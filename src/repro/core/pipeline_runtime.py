"""SPMD pipeline executor: runs a :class:`TaskTable` under a
partial-manual ``jax.shard_map`` (manual over the pipeline axis, auto
TP/DP inside stages).

Layer layout: each (device ``d``, chunk ``c``) position holds the
contiguous block of ``K = L_pad/(v*P)`` layers starting at
``placement.block(d, c) * K`` — interleaved striping (block
``c*P + d``) unless the schedule carries a placement (the V-shape
family's fold-back puts blocks ``d`` and ``2P-1-d`` on device ``d``).
K must be a multiple of the arch's *structural* period (attention/SSM
interleave, MoE cadence); local/global attention patterns and padding
("null layers", gate=0 passthrough) ride along as per-layer data flags,
so e.g. gemma3's 5:1 pattern needs no structural alignment.

Backward is boundary + rematerialize: each stage stores only its chunk's
input payload and recomputes internals inside ``jax.vjp`` at B-task time
(Chronos-Recomp semantics; the stored-residual optimization for deep
chunks is a §Perf item).  Embedding / head / encoder parameters are
replicated across stages, used only where relevant, and their gradients
psum over the pipe axis — this also gives tied embeddings for free.

Split backward (schedules with ``W`` tasks, e.g. ``zb_h1`` /
``chronos_zb``): the B tick runs ``jax.vjp`` w.r.t. the *boundary
payload only* — producing the input gradient that unblocks the upstream
stage — and stashes its residuals (boundary payload + upstream gradient)
into a W-stash ring sized by the task-table compiler.  The matching W
tick re-linearizes w.r.t. the *parameters only* from the stash and
accumulates weight gradients.  Both halves linearize the identical
forward function at the identical primal point, so split gradients match
the fused path to float determinism.

Explicit recompute (schedules with ``R`` tasks, e.g. ``chronos_recomp``):
the R tick retires the chunk's boundary checkpoint from the activation
ring (F->R lifetime) and hands it to the rematerialization ring (R->B)
that the chunk's backward consumes.  Because JAX autodiff is functional,
the forward replay itself is fused into the B tick's ``jax.vjp`` — the
same boundary-plus-rematerialize linearization every backward here runs
under ``jax.checkpoint`` — so the compiled gradient math is *identical*
to the no-recompute path and ``chronos_recomp(rho)`` gradients match
``chronos`` bitwise (``tests/helpers/split_fused_check.py --pair
recomp`` asserts maxerr == 0).  The R task's scheduled duration carries
the replay cost in the schedule IR / analytic timeline; a future
stored-residual path would move the replay FLOPs into the R tick by
stashing linearization residuals instead of the boundary payload.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.placement import Placement
from repro.core.schedules import get_schedule
from repro.core.tasktable import (B_OPS, BWD_FIRST, BWD_LAST, BWD_MID,
                                  F_OPS, FWD_FIRST, FWD_LAST, FWD_MID,
                                  IDLE, LOCAL_SENDS, R_OPS, RCP_MID,
                                  SEND_B_DOWN, SEND_B_LOC, SEND_BWD,
                                  SEND_F_LOC, SEND_F_UP, SEND_FWD,
                                  SEND_HOPB, SEND_HOPF, SEND_NONE,
                                  TaskTable,
                                  W_OPS, WGT_FIRST,
                                  WGT_LAST, WGT_MID, build_task_table,
                                  factor_phases, replay_phases)
from repro.kernels import check_vma
from repro.models import backend as compute_backend
from repro.models import layers as L
from repro.models.backend import get_backend
from repro.models.sharding import match_vma, shard
from repro.models.transformer import _init_layer

#: executor selection: "phase" (phase-compiled, the default) or "legacy"
#: (the pre-phase per-tick interpreter, kept for A/B benchmarking —
#: ``benchmarks/pipeline_exec.py`` measures both).
EXECUTOR_ENV = "REPRO_PIPELINE_EXECUTOR"

#: wire-protocol switch point (bytes of all-gathered payload per tick):
#: at or below this, the phase executors use the single-collective
#: all_gather exchange; above it, the bandwidth-exact rotation pair.
#: Override with the REPRO_EXCHANGE_AG_MAX env var.
EXCHANGE_AG_MAX = 4 << 20


def _exchange_ag_max() -> int:
    return int(os.environ.get("REPRO_EXCHANGE_AG_MAX",
                              str(EXCHANGE_AG_MAX)))


def _build_route(tab: "TaskTable", P_: int, pp: str, snds, use_ag: bool,
                 s_idx):
    """Shared wire protocol of the phase executors (core + seqpipe).

    Two statically-chosen cross-device forms (see the module
    docstrings):

    - *rotation pair*: hop wraps fold into full ring rotations and
      same-direction F/B payloads stack — at most one ``ppermute`` per
      direction per tick, no bandwidth waste (large payloads).
    - *single-collective exchange* (``use_ag``): every device's send
      code is static table data, so receivers select their arrivals
      from ONE ``all_gather`` of the raw wire payload — one rendezvous
      per tick, which dominates when the per-tick collective is
      latency- rather than bandwidth-bound (small payloads).

    Channels the table never uses compile away.  Returns
    ``(route_xdev, route_local)``:

    - ``route_xdev(fq, bq, out, row_all, row) -> (fq, bq)`` runs the
      collective and lands cross-device arrivals (columns 6/7/9/10);
    - ``route_local(fq, bq, out, row) -> (fq, bq)`` lands the
      device-local channels (columns 8/11), no collective.

    The synchronous executor composes both on the current tick's
    payload; the double-buffered executor feeds ``route_xdev`` the
    *previous* tick's payload and row (deferring delivery by one tick,
    which is what lets XLA overlap the collective with this tick's
    compute) while local channels keep same-tick delivery."""

    def wr(buf, val, i):
        return jax.lax.dynamic_update_index_in_dim(buf, val, i, 0)

    def qwrite(qbuf, slot, val, depth):
        with jax.named_scope("wire"):
            return wr(qbuf, val, jnp.where(slot < 0, depth, slot))

    def sel_from(payload, code_val, want):
        have = [cd for cd in want if cd in snds]
        if not have:
            return None
        with jax.named_scope("wire"):
            m = functools.reduce(jnp.logical_or,
                                 [code_val == cd for cd in have])
            return jnp.where(m, payload, jnp.zeros_like(payload))

    def route_rotations(fq, bq, out, row_all, row):
        snd = row[5]
        rot_dn = [(i, (i + 1) % P_) for i in range(P_)]
        rot_up = [(i, (i - 1) % P_) for i in range(P_)]
        for perm, f_want, b_want, rcf_c, rcb_c in (
                (rot_dn, (SEND_FWD, SEND_HOPF), (SEND_B_DOWN,), 6, 9),
                (rot_up, (SEND_F_UP,), (SEND_BWD, SEND_HOPB), 7, 10)):
            fp = sel_from(out, snd, f_want)
            bp_ = sel_from(out, snd, b_want)
            if fp is not None and bp_ is not None:
                mv = _ppermute(jnp.stack([fp, bp_]), pp, perm)
                fp, bp_ = mv[0], mv[1]
            elif fp is not None:
                fp = _ppermute(fp, pp, perm)
            elif bp_ is not None:
                bp_ = _ppermute(bp_, pp, perm)
            if fp is not None:
                fq = qwrite(fq, row[rcf_c], fp, tab.fq_depth)
            if bp_ is not None:
                bq = qwrite(bq, row[rcb_c], bp_, tab.bq_depth)
        return fq, bq

    def gather_wire(out):
        if P_ == 1:
            return out[None]
        with jax.named_scope("exchange"):
            return jax.lax.all_gather(out, pp, axis=0, tiled=False)

    def route_exchange(fq, bq, out, row_all, row):
        outs = gather_wire(out)
        prev = (s_idx + P_ - 1) % P_
        nxt = (s_idx + 1) % P_
        out_dn, snd_dn = outs[prev], row_all[prev, 5]
        out_up, snd_up = outs[nxt], row_all[nxt, 5]
        for payload, code_val, want, qname, col in (
                (out_dn, snd_dn, (SEND_FWD, SEND_HOPF), "f", 6),
                (out_dn, snd_dn, (SEND_B_DOWN,), "b", 9),
                (out_up, snd_up, (SEND_F_UP,), "f", 7),
                (out_up, snd_up, (SEND_BWD, SEND_HOPB), "b", 10)):
            arr = sel_from(payload, code_val, want)
            if arr is None:
                continue
            if qname == "f":
                fq = qwrite(fq, row[col], arr, tab.fq_depth)
            else:
                bq = qwrite(bq, row[col], arr, tab.bq_depth)
        return fq, bq

    # no cross-device send code in the whole table (P=1, or an entirely
    # device-local placement): the collective route short-circuits away,
    # deferred or not — mirroring _ppermute's identity-perm skip
    has_xdev = bool(frozenset(snds) - frozenset(LOCAL_SENDS))

    def route_xdev(fq, bq, out, row_all, row):
        if not has_xdev:
            return fq, bq
        return (route_exchange if use_ag
                else route_rotations)(fq, bq, out, row_all, row)

    def route_local(fq, bq, out, row):
        snd = row[5]
        fl = sel_from(out, snd, (SEND_F_LOC,))
        if fl is not None:
            fq = qwrite(fq, row[8], fl, tab.fq_depth)
        bl = sel_from(out, snd, (SEND_B_LOC,))
        if bl is not None:
            bq = qwrite(bq, row[11], bl, tab.bq_depth)
        return fq, bq

    route_xdev.has_xdev = has_xdev
    return route_xdev, route_local


def pipeline_period(cfg: ModelConfig) -> int:
    """Structural period (param-tree shape changes); attention local/global
    patterns are data flags, not structure."""
    p = 1
    if cfg.ssm is not None and cfg.ssm.attn_period:
        p = _lcm(p, cfg.ssm.attn_period)
    if cfg.moe is not None and cfg.moe.layer_period > 1:
        p = _lcm(p, cfg.moe.layer_period)
    return p


def _lcm(a, b):
    return a * b // math.gcd(a, b)


@dataclass(frozen=True)
class StageLayout:
    P: int
    v: int
    L: int              # real layers
    L_pad: int
    K: int              # layers per (device, chunk) block
    period: int         # structural period
    M: int              # periods per block = K // period
    # layer-block <-> device assignment; None = interleaved striping
    # (block c*P + d at (device d, chunk c)), the pre-placement layout
    placement: Optional[Placement] = None

    @property
    def pl(self) -> Placement:
        return self.placement if self.placement is not None \
            else Placement(self.P, self.v)

    @staticmethod
    def build(cfg: ModelConfig, P: int, v: int,
              placement: Optional[Placement] = None) -> "StageLayout":
        per = pipeline_period(cfg)
        quantum = P * v * per
        L_pad = -(-cfg.num_layers // quantum) * quantum
        K = L_pad // (P * v)
        return StageLayout(P=P, v=v, L=cfg.num_layers, L_pad=L_pad, K=K,
                           period=per, M=K // per, placement=placement)

    def global_idx(self, d: int, c: int, j: int) -> int:
        """Global layer index of local layer ``j`` of the block at
        (device ``d``, chunk ``c``) — the placement's block assignment
        (``(c*P + d)*K + j`` under interleaved striping)."""
        return self.pl.block(d, c) * self.K + j

    def flags(self, cfg: ModelConfig) -> Dict[str, np.ndarray]:
        """window [P,v,M,period] int32; gate [P,v,M,period] f32 —
        indexed by (device, chunk), following the placement."""
        win = np.zeros((self.P, self.v, self.M, self.period), np.int32)
        gate = np.zeros((self.P, self.v, self.M, self.period), np.float32)
        for d in range(self.P):
            for c in range(self.v):
                for mi in range(self.M):
                    for j in range(self.period):
                        g = self.global_idx(d, c, mi * self.period + j)
                        if g < self.L:
                            gate[d, c, mi, j] = 1.0
                            win[d, c, mi, j] = (
                                0 if cfg.layer_is_global(g)
                                else cfg.sliding_window)
        return {"window": win, "gate": gate}


# ---------------------------------------------------------------------------
# parameter init (stage-stacked)
# ---------------------------------------------------------------------------

def remap_blocks(blocks, layout_src: StageLayout, layout_dst: StageLayout):
    """Re-index stacked block leaves ``[P, v, M, ...]`` from one
    placement's (device, chunk) layout to another's, preserving the
    global layer each position holds — so two pipeline runs under
    different placements compute the *same network* from remapped
    parameters (and their gradients compare position-for-position
    after the inverse remap)."""
    assert (layout_src.P, layout_src.v, layout_src.K) == \
        (layout_dst.P, layout_dst.v, layout_dst.K)
    P, v = layout_src.P, layout_src.v
    src_of = {layout_src.pl.block(d, c): (d, c)
              for d in range(P) for c in range(v)}
    idx_d = np.zeros((P, v), np.int64)
    idx_c = np.zeros((P, v), np.int64)
    for d in range(P):
        for c in range(v):
            idx_d[d, c], idx_c[d, c] = src_of[layout_dst.pl.block(d, c)]

    def one(a):
        return a[idx_d, idx_c]

    return [jax.tree.map(one, t) for t in blocks]


def remap_blocks_elastic(blocks, layout_src: StageLayout,
                         layout_dst: StageLayout, init_blocks=None):
    """Re-index stacked block leaves across *different* layouts — the
    elastic live-migration path.  Unlike :func:`remap_blocks` (same
    (P, v, K), placement conversion only), source and destination may
    differ in P, v, and placement: every destination position
    ``(d, c, mi)`` of period-phase ``j`` holds global layer
    ``dst.pl.block(d, c) * dst.K + mi * period + j`` and is gathered
    from wherever the source layout stored that layer.  K is always a
    multiple of the structural period on both sides, so a layer keeps
    its period-phase and each phase's tree remaps with one shared index
    triple.

    Destination positions whose global layer lies beyond the source's
    padded span (L_pad can shrink when P does) are padding layers
    (gate 0, no forward effect, zero grads); they are filled from
    ``init_blocks`` — a freshly-initialized parameter/zeroed-moment
    tree under ``layout_dst`` — which is required exactly then."""
    per = layout_src.period
    assert per == layout_dst.period and layout_src.L == layout_dst.L, \
        "elastic remap requires the same model (period, num_layers)"
    Ps, vs = layout_src.P, layout_src.v
    Pd, vd, Md = layout_dst.P, layout_dst.v, layout_dst.M
    Ks = layout_src.K
    src_of = {layout_src.pl.block(d, c): (d, c)
              for d in range(Ps) for c in range(vs)}
    idx_d = np.zeros((Pd, vd, Md), np.int64)
    idx_c = np.zeros((Pd, vd, Md), np.int64)
    idx_m = np.zeros((Pd, vd, Md), np.int64)
    have = np.zeros((Pd, vd, Md), bool)
    for d in range(Pd):
        for c in range(vd):
            for mi in range(Md):
                g = layout_dst.pl.block(d, c) * layout_dst.K + mi * per
                if g < layout_src.L_pad:
                    blk, within = divmod(g, Ks)
                    idx_d[d, c, mi], idx_c[d, c, mi] = src_of[blk]
                    idx_m[d, c, mi] = within // per
                    have[d, c, mi] = True
    if bool(have.all()):
        def one(a):
            return a[idx_d, idx_c, idx_m]
        return [jax.tree.map(one, t) for t in blocks]
    assert init_blocks is not None, \
        "destination has padding positions absent from the source; " \
        "pass init_blocks (freshly-initialized under layout_dst)"

    def one2(a, a0):
        g = a[idx_d, idx_c, idx_m]
        mask = have.reshape(have.shape + (1,) * (g.ndim - 3))
        return jnp.where(mask, g, a0)

    return [jax.tree.map(one2, t, t0)
            for t, t0 in zip(blocks, init_blocks)]


def init_pipeline_params(key, cfg: ModelConfig, layout: StageLayout):
    """Returns (params, logical_specs).  Block leaves are
    [P, v, M, ...] indexed by (device, chunk) under ``layout``'s
    placement; embed/head/final_norm/encoder replicated over pp."""
    ks = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.param_dtype)

    blocks, bspecs = [], []
    for j in range(layout.period):
        total = layout.P * layout.v * layout.M
        keys = jax.random.split(jax.random.fold_in(ks[0], j), total)
        flat = jax.vmap(lambda k: _init_layer(k, cfg, j)[0])(keys)
        stacked = jax.tree.map(
            lambda a: a.reshape((layout.P, layout.v, layout.M) + a.shape[1:]),
            flat)
        _, sj = _init_layer(keys[0], cfg, j)
        blocks.append(stacked)
        bspecs.append(jax.tree.map(
            lambda sp: ("pp", None, None) + tuple(sp), sj,
            is_leaf=lambda x: isinstance(x, tuple)))

    params: Dict[str, Any] = {"blocks": blocks}
    specs: Dict[str, Any] = {"blocks": bspecs}
    params["embed"], specs["embed"] = L.init_embed(
        ks[1], cfg.vocab_size, cfg.d_model, dtype, cfg.tie_embeddings)
    params["final_norm"], specs["final_norm"] = L.init_rmsnorm(
        cfg.d_model, dtype)
    if cfg.encdec is not None:
        from repro.models.transformer import LM
        lm = LM(cfg)
        full, full_specs = lm.init(ks[2])
        params["encoder"] = full["encoder"]
        params["enc_norm"] = full["enc_norm"]
        specs["encoder"] = full_specs["encoder"]
        specs["enc_norm"] = full_specs["enc_norm"]
    return params, specs


# ---------------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------------

@dataclass
class PipelineSpec:
    cfg: ModelConfig
    layout: StageLayout
    table: TaskTable
    mbB: int                    # global microbatch size (sequences)
    S: int                      # token positions fed to the stack
    prefix: int                 # vlm patch prefix length
    enc_len: int                # whisper encoder positions (0 if none)
    pp_axis: str = "pp"
    aux_weight: float = 0.01
    n_seq: int = 1              # sequence chunks (repro.seqpipe)
    kernels: str = "xla"        # compute backend (repro.models.backend)
    #: boundary-payload wire dtype: "fp32" (exact bitcast, the
    #: bitwise-equivalence baseline), "bf16" (cast + bitcast, half the
    #: words), or "int8" (per-row symmetric quantization, scale riding
    #: in two leading uint16 words per row per leaf — ~quarter width).
    wire: str = "fp32"
    #: int width of the compressed shared-parameter gradient psum over
    #: the pipe axis (``optim.compression.compressed_psum``), or None
    #: for the exact fp32 psum.  Requires the caller to thread
    #: persistent error-feedback state (see :func:`init_psum_ef`).
    grad_psum_bits: Optional[int] = None


def make_pipeline_spec(cfg: ModelConfig, *, P: int, v: int, m: int,
                       microbatch: int, seq_len: int, schedule: str,
                       pp_axis: str = "pp", n_seq: int = 1,
                       kernels: str = "xla", wire: str = "fp32",
                       overlap: bool = False,
                       grad_psum_bits: Optional[int] = None,
                       **sched_kw) -> PipelineSpec:
    seq_schedules = ("seq1f1b", "chronos_seq")
    if schedule in seq_schedules:
        sched_kw["n_seq"] = n_seq
    else:
        assert n_seq == 1, f"{schedule} is not sequence-chunked"
    sched = get_schedule(schedule, P, m, **({"v": v} if schedule in
                                            ("chronos", "interleaved",
                                             "chronos_zero2", "chronos_zb",
                                             "chronos_recomp",
                                             "chronos_seq")
                                            else {}),
                         **sched_kw)
    if schedule in ("1f1b", "zb_h1", "seq1f1b"):
        assert v == 1, f"{schedule} is a v=1 schedule, got v={v}"
    assert sched.v == v, \
        f"{schedule} constructs v={sched.v}, spec asked for v={v}"
    # the layer->device assignment follows the schedule's placement
    # (interleaved striping unless the generator carries one, e.g. the
    # V-shape family's fold-back)
    layout = StageLayout.build(cfg, P, v, placement=sched.placement)
    # the wire's delivery contract: synchronous (each tick's exchange
    # delivers this tick's payload) unless ``overlap=True`` asks for the
    # double-buffered table — both keep the per-device op order, so
    # gradients are bitwise equal across the pair
    assert wire in ("fp32", "bf16", "int8"), f"unknown wire {wire!r}"
    table = build_task_table(sched, overlap=overlap)
    prefix = cfg.vision.num_patches if cfg.vision is not None else 0
    enc_len = cfg.encdec.num_frames if cfg.encdec is not None else 0
    if n_seq > 1:
        # the seq executor threads a KV prefix through chunked causal
        # attention — cross-token state beyond KV (SSM scans, encoder
        # cross-attention, VLM prefixes, MoE aux weighting) is out of
        # scope for the seq-chunked runtime
        assert cfg.ssm is None and cfg.encdec is None \
            and cfg.vision is None and cfg.moe is None, \
            f"seq-chunked runtime supports dense attention LMs, " \
            f"got {cfg.name}"
        assert (seq_len - 1) % n_seq == 0, \
            f"seq_len-1 = {seq_len - 1} not divisible by n_seq={n_seq}"
        assert not table.has_w, \
            "split-backward seq schedules are IR/table-only for now"
    get_backend(kernels)        # validate the flag early
    return PipelineSpec(cfg=cfg, layout=layout, table=table, mbB=microbatch,
                        S=seq_len - 1 + prefix, prefix=prefix,
                        enc_len=enc_len, pp_axis=pp_axis, n_seq=n_seq,
                        kernels=kernels, wire=wire,
                        grad_psum_bits=grad_psum_bits)


def _zero_payload(spec: PipelineSpec, dtype):
    pay = {"x": jnp.zeros((spec.mbB, spec.S, spec.cfg.d_model), dtype),
           "aux": jnp.zeros((1,), jnp.float32)}
    if spec.enc_len:
        pay["enc"] = jnp.zeros((spec.mbB, spec.enc_len, spec.cfg.d_model),
                               dtype)
    return pay


def _chunk_fwd(spec: PipelineSpec, block_params_c, flags_c, payload):
    """Run this stage's chunk over the payload (the shared ChunkBody
    seam, parameterized by ``spec.kernels``).  block_params_c: leaves
    [M, ...]; flags_c: {window, gate} [M, period]."""
    return compute_backend.chunk_fwd(spec, block_params_c, flags_c,
                                     payload)


def _embed_tokens(spec: PipelineSpec, params, tokens, patch=None,
                  frames=None):
    cfg = spec.cfg
    x = L.embed(params["embed"], tokens)
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if patch is not None:
        x = jnp.concatenate([patch.astype(x.dtype), x], axis=1)
    x = x.astype(jnp.dtype(cfg.compute_dtype))
    x = shard(x, "dp", None, None)
    pay = {"x": x, "aux": jnp.zeros((1,), jnp.float32)}
    if spec.enc_len:
        from repro.models.transformer import LM
        enc = LM(cfg).encode(params, frames)
        pay["enc"] = enc
    return pay


def _head_loss(spec: PipelineSpec, params, payload, labels, loss_mask):
    return compute_backend.head_loss(spec, params, payload, labels,
                                     loss_mask)


def make_train_grads_fn(spec: PipelineSpec, mesh,
                        executor: Optional[str] = None):
    """Returns fn(params, batch) -> (grads, metrics) running the full
    pipeline schedule.  batch: tokens [m, mbB, S_tokens] (+ optional
    patch_embeds [m, mbB, prefix, d], frame_embeds [m, mbB, enc_len, d],
    loss_mask [m, mbB, S_tokens-1]).

    ``executor`` selects the compiled form (default from the
    ``REPRO_PIPELINE_EXECUTOR`` env var, else ``"phase"``):

    - ``"phase"`` — the phase-compiled executor: unified op branches
      (one masked forward body instead of first/mid/last triplicates,
      traced once), warmup / steady-period / cooldown scans from
      :func:`repro.core.tasktable.factor_phases`, byte-packed boundary
      payloads, and at most two ``ppermute`` s per tick (hop wraps fold
      into full ring rotations).
    - ``"legacy"`` — the pre-phase per-tick interpreter (a ~13-way
      switch re-tracing the chunk body per branch and up to five
      ``ppermute`` s per tick); kept so ``benchmarks/pipeline_exec.py``
      can record both sides of the comparison.

    Both executors compute identical gradients for a given schedule up
    to XLA fusion order; the cross-schedule equivalence pairs
    (``tests/helpers/split_fused_check.py``) hold at their original
    tolerances — bitwise for the recomp pair — under either.

    Sequence-chunked specs (``spec.n_seq > 1``) dispatch to the
    :mod:`repro.seqpipe` executor, which adds the KV-carry / dKV rings
    for chunked causal attention."""
    if executor is None:
        executor = os.environ.get(EXECUTOR_ENV, "phase")
    if executor not in ("phase", "legacy"):
        raise ValueError(f"unknown executor {executor!r}: "
                         f"expected 'phase' or 'legacy'")
    if executor == "legacy" and (_wire_of(spec) != "fp32"
                                 or spec.grad_psum_bits):
        raise ValueError("wire compression (wire=/grad_psum_bits=) "
                         "requires the 'phase' executor — the legacy "
                         "interpreter moves unpacked payload trees")
    if spec.n_seq > 1:
        if spec.grad_psum_bits:
            raise ValueError("compressed gradient psum is not "
                             "implemented for sequence-chunked specs")
        from repro.seqpipe.runtime import make_seq_train_grads_fn
        return make_seq_train_grads_fn(spec, mesh, executor=executor)
    if executor == "phase":
        return _make_train_grads_phase(spec, mesh)
    return _make_train_grads_legacy(spec, mesh)


def make_train_update_fn(spec: PipelineSpec, mesh, ocfg, m: int,
                         executor: Optional[str] = None):
    """Phase executor with the optimizer fused into the pipeline
    program: returns ``fn(params, opt_state, batch) -> (params,
    opt_state, metrics)``.

    The AdamW step (``kernels/fused_adamw``) runs inside the shard_map
    region right after the tick scan, on the stage-local gradient
    accumulators — eliminating the separate optimizer phase that
    ``make_train_grads_fn`` callers otherwise run on the gathered
    gradient tree.  This is the natural companion of the split-backward
    families (``zb_h1``, ``chronos_zb``, ``v_*``), whose W ticks already
    finish each stage's weight gradients inside the schedule; it is
    mathematically the post-accumulation update (AdamW is nonlinear in
    the summed gradient, so per-W-tick application would change the
    math).  ``m`` is the gradient-mean divisor (number of microbatches);
    ``opt_state`` is :func:`repro.optim.adamw.adamw_init` of the params.
    The trajectory matches the phase-separate ``astype(f32)/m ->
    adamw_update(use_kernel=True)`` path step-count-exact.

    Only the ``"phase"`` executor supports fusion; sequence-chunked
    specs (``n_seq > 1``) keep the phase-separate optimizer."""
    if executor is None:
        executor = os.environ.get(EXECUTOR_ENV, "phase")
    if executor != "phase":
        raise ValueError("in-executor optimizer fusion requires the "
                         f"'phase' executor, got {executor!r}")
    if spec.n_seq > 1:
        raise ValueError("in-executor optimizer fusion is not "
                         "implemented for sequence-chunked specs")
    return _make_train_grads_phase(spec, mesh, ocfg=ocfg, opt_m=m)


def _make_train_grads_legacy(spec: PipelineSpec, mesh):
    """The pre-phase per-tick interpreter (see
    :func:`make_train_grads_fn`, ``executor="legacy"``)."""
    cfg = spec.cfg
    tab = spec.table
    P_, v = tab.P, tab.v
    pp = spec.pp_axis
    kernels_on = get_backend(spec.kernels).fuses
    table_arr = jnp.asarray(tab.arrays())              # [T, P, 16]
    # static routing channels (legacy interleaved tables use only
    # f-down / b-up / wrap; V-shape adds f-up / b-down / local and
    # never wraps) — unused routes compile away entirely
    snd_codes = set(int(x) for x in np.unique(tab.send))
    use_f_dn = SEND_FWD in snd_codes
    use_f_up = SEND_F_UP in snd_codes
    use_f_loc = SEND_F_LOC in snd_codes
    use_b_up = SEND_BWD in snd_codes
    use_b_dn = SEND_B_DOWN in snd_codes
    use_b_loc = SEND_B_LOC in snd_codes
    use_hop = (SEND_HOPF in snd_codes) or (SEND_HOPB in snd_codes)
    act_offsets = np.zeros(v, np.int64)
    total_act = 0
    for c in range(v):
        act_offsets[c] = total_act
        total_act += tab.act_depth[c]
    act_offsets = jnp.asarray(act_offsets)
    split = tab.has_w                     # split-backward (B/W) schedule
    w_offsets = np.zeros(v, np.int64)
    total_wstash = 0
    if split:
        for c in range(v):
            w_offsets[c] = total_wstash
            total_wstash += tab.wstash_depth[c]
    w_offsets = jnp.asarray(w_offsets)
    remat = tab.has_r                     # explicit-recompute (R) schedule
    r_offsets = np.zeros(v, np.int64)
    total_rmt = 0
    if remat:
        for c in range(v):
            r_offsets[c] = total_rmt
            total_rmt += tab.rmt_depth.get(c, 0)
    r_offsets = jnp.asarray(r_offsets)
    flags_np = spec.layout.flags(cfg)

    def spmd(stage_iota, params, batch):
        # stage index from a pp-sharded iota (local shape [1]) rather
        # than lax.axis_index: the latter lowers to a PartitionId op
        # that older XLA SPMD partitioners reject under partial-auto
        # shard_map (the dp/tp axes stay auto).
        s_idx = stage_iota[0]
        blocks = [jax.tree.map(lambda a: a[0], t) for t in params["blocks"]]
        # ^ in_specs P("pp") leaves local shape [1, v, M, ...] -> strip
        flags = {k: jnp.asarray(vv)[s_idx] for k, vv in flags_np.items()}
        shared = {k: params[k] for k in params if k != "blocks"}
        dtype = jnp.dtype(cfg.compute_dtype)

        def to_varying(a):
            # varying over pp like the stage-sharded inputs (a no-op in a
            # region that interprets kernels, see kernels.check_vma)
            return match_vma(a, stage_iota)

        def vary(x):
            return jax.tree.map(to_varying, x)

        def fwd_fn(blocks_c, shared_p, payload, flags_c):
            return vary(_chunk_fwd(spec, blocks_c, flags_c, payload))

        def first_fn(blocks_c, shared_p, tokens, patch, frames, flags_c):
            pay = _embed_tokens(spec, shared_p, tokens, patch, frames)
            return vary(_chunk_fwd(spec, blocks_c, flags_c, pay))

        def last_fn(blocks_c, shared_p, payload, labels, mask, flags_c):
            out = _chunk_fwd(spec, blocks_c, flags_c, payload)
            ce = _head_loss(spec, shared_p, out, labels, mask)
            return to_varying(ce)

        zero_pay = vary(_zero_payload(spec, dtype))
        zero_blocks_g = jax.tree.map(jnp.zeros_like, blocks)
        zero_shared_g = jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), shared)

        def pin_buf(t):
            """Payload ring buffers are scan carries; without an explicit
            constraint XLA replicates them over data/model — pin
            [slots, mbB, S, d] to batch-over-dp."""
            def one(a):
                if a.ndim >= 3:
                    return shard(a, None, "dp", None, None)
                return a
            return jax.tree.map(one, t)

        def carry_init():
            carry = {
                "fq": pin_buf(jax.tree.map(
                    lambda a: jnp.zeros((tab.fq_depth,) + a.shape, a.dtype),
                    zero_pay)),
                "bq": pin_buf(jax.tree.map(
                    lambda a: jnp.zeros((tab.bq_depth,) + a.shape, a.dtype),
                    zero_pay)),
                "act": pin_buf(jax.tree.map(
                    lambda a: jnp.zeros((total_act,) + a.shape, a.dtype),
                    zero_pay)),
                "gb": zero_blocks_g,
                "gs": zero_shared_g,
                "loss": jnp.zeros((), jnp.float32),
                "nloss": jnp.zeros((), jnp.float32),
            }
            if split:
                # W-stash rings: boundary payload + upstream gradient,
                # resident from the B tick until the matching W tick
                carry["wx"] = pin_buf(jax.tree.map(
                    lambda a: jnp.zeros((total_wstash,) + a.shape, a.dtype),
                    zero_pay))
                carry["wdy"] = pin_buf(jax.tree.map(
                    lambda a: jnp.zeros((total_wstash,) + a.shape, a.dtype),
                    zero_pay))
            if remat:
                # remat rings: boundary payloads of rematerialized
                # chunks, resident from the R tick until the B tick
                carry["rmt"] = pin_buf(jax.tree.map(
                    lambda a: jnp.zeros((total_rmt,) + a.shape, a.dtype),
                    zero_pay))
            return carry

        def get_mb(arr, mb):
            return jax.lax.dynamic_index_in_dim(arr, mb, 0, keepdims=False)

        def tick(carry, t):
            row = table_arr[t, s_idx]                  # [16]
            op, c, mb = row[0], row[1], row[2]
            src, aslot, snd = row[3], row[4], row[5]

            blocks_c = [jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, c, 0, False), t_)
                for t_ in blocks]
            flags_c = {k: jax.lax.dynamic_index_in_dim(vv, c, 0, False)
                       for k, vv in flags.items()}
            x_in = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, jnp.maximum(src, 0), 0, False), carry["fq"])
            dy_in = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, jnp.maximum(src, 0), 0, False), carry["bq"])
            gslot = act_offsets[c] + jnp.maximum(aslot, 0)
            act_in = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, gslot, 0, False),
                carry["act"])
            if remat:
                # rematerialized chunks retire their act slot at the R
                # tick; their B reads the boundary from the remat ring
                grm = r_offsets[c] + jnp.maximum(row[13], 0)
                rmt_in = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, grm, 0,
                                                           False),
                    carry["rmt"])
                bnd_in = jax.tree.map(
                    lambda r_, a_: jnp.where(row[13] >= 0, r_, a_),
                    rmt_in, act_in)
            else:
                bnd_in = act_in
            tokens = get_mb(batch["tokens"], mb)
            labels = tokens[:, 1:]
            tok_in = tokens[:, :-1]
            patch = (get_mb(batch["patch_embeds"], mb)
                     if "patch_embeds" in batch else None)
            frames = (get_mb(batch["frame_embeds"], mb)
                      if "frame_embeds" in batch else None)
            mask = (get_mb(batch["loss_mask"], mb)
                    if "loss_mask" in batch else None)

            def wr_act(carry, pay):
                return dict(carry, act=jax.tree.map(
                    lambda buf, p: jax.lax.dynamic_update_index_in_dim(
                        buf, p, gslot, 0), carry["act"], pay))

            def br_idle(carry):
                return carry, zero_pay

            def br_fwd_mid(carry):
                out = fwd_fn(blocks_c, shared, x_in, flags_c)
                return wr_act(carry, x_in), out

            def br_fwd_first(carry):
                out = first_fn(blocks_c, shared, tok_in, patch, frames,
                               flags_c)
                return carry, out

            def br_fwd_last(carry):
                out = fwd_fn(blocks_c, shared, x_in, flags_c)
                ce = _head_loss(spec, shared, out, labels, mask)
                carry = wr_act(carry, x_in)
                return dict(carry, loss=carry["loss"] + ce,
                            nloss=carry["nloss"] + 1.0), zero_pay

            def _add_block_grads(carry, gb_c):
                gb = jax.tree.map(
                    lambda g, d: jax.lax.dynamic_update_index_in_dim(
                        g, jax.lax.dynamic_index_in_dim(g, c, 0, False) + d,
                        c, 0),
                    carry["gb"], gb_c)
                return dict(carry, gb=gb)

            def _add_shared_grads(carry, gs):
                return dict(carry, gs=jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), carry["gs"], gs))

            def br_bwd_mid(carry):
                dy = vary(dict(dy_in))
                _, vjp = jax.vjp(
                    lambda bp, pay: fwd_fn(bp, shared, pay, flags_c),
                    vary(blocks_c), vary(bnd_in))
                gb_c, dx = vjp(dy)
                return _add_block_grads(carry, gb_c), dx

            def br_bwd_first(carry):
                dy = vary(dict(dy_in))
                _, vjp = jax.vjp(
                    lambda bp, sp: first_fn(bp, sp, tok_in, patch, frames,
                                            flags_c),
                    vary(blocks_c), vary(shared))
                gb_c, gs = vjp(dy)
                carry = _add_block_grads(carry, gb_c)
                return _add_shared_grads(carry, gs), zero_pay

            def br_bwd_last(carry):
                _, vjp = jax.vjp(
                    lambda bp, sp, pay: last_fn(bp, sp, pay, labels, mask,
                                                flags_c),
                    vary(blocks_c), vary(shared), vary(bnd_in))
                gb_c, gs, dx = vjp(to_varying(jnp.ones((), jnp.float32)))
                carry = _add_block_grads(carry, gb_c)
                return _add_shared_grads(carry, gs), dx

            branches = [br_idle, br_fwd_mid, br_fwd_first, br_fwd_last]
            if not split:
                branches += [br_bwd_mid, br_bwd_first, br_bwd_last]
            else:
                # ---- split backward: B = input grad + stash, W = weight
                # grad from stash.  Both halves linearize the same forward
                # at the same primal point as the fused path.
                gw = w_offsets[c] + jnp.maximum(row[12], 0)

                def stash_rd(buf):
                    return jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(
                            a, gw, 0, False), buf)

                def upd_stash(buf, p):
                    return jax.tree.map(
                        lambda bb, q: jax.lax.dynamic_update_index_in_dim(
                            bb, q, gw, 0), buf, p)

                def br_bwdi_mid(carry):
                    dy = vary(dict(dy_in))
                    _, vjp = jax.vjp(
                        lambda pay: fwd_fn(blocks_c, shared, pay, flags_c),
                        vary(bnd_in))
                    (dx,) = vjp(dy)
                    carry = dict(carry, wx=upd_stash(carry["wx"],
                                                     vary(bnd_in)),
                                 wdy=upd_stash(carry["wdy"], dy))
                    return carry, dx

                def br_bwdi_first(carry):
                    # stage-0 shallow chunk: the block input is the token
                    # batch (re-fetched at W time), so the B tick only
                    # stashes the upstream gradient.
                    dy = vary(dict(dy_in))
                    return dict(carry, wdy=upd_stash(carry["wdy"], dy)), \
                        zero_pay

                def br_bwdi_last(carry):
                    # loss head: the W seed is the constant 1.0, so only
                    # the boundary payload needs stashing.
                    _, vjp = jax.vjp(
                        lambda pay: last_fn(blocks_c, shared, pay, labels,
                                            mask, flags_c),
                        vary(bnd_in))
                    (dx,) = vjp(to_varying(jnp.ones((), jnp.float32)))
                    return dict(carry, wx=upd_stash(carry["wx"],
                                                    vary(bnd_in))), dx

                def br_w_mid(carry):
                    pay = vary(stash_rd(carry["wx"]))
                    dy = vary(stash_rd(carry["wdy"]))
                    _, vjp = jax.vjp(
                        lambda bp: fwd_fn(bp, shared, pay, flags_c),
                        vary(blocks_c))
                    (gb_c,) = vjp(dy)
                    return _add_block_grads(carry, gb_c), zero_pay

                def br_w_first(carry):
                    dy = vary(stash_rd(carry["wdy"]))
                    _, vjp = jax.vjp(
                        lambda bp, sp: first_fn(bp, sp, tok_in, patch,
                                                frames, flags_c),
                        vary(blocks_c), vary(shared))
                    gb_c, gs = vjp(dy)
                    carry = _add_block_grads(carry, gb_c)
                    return _add_shared_grads(carry, gs), zero_pay

                def br_w_last(carry):
                    pay = vary(stash_rd(carry["wx"]))
                    _, vjp = jax.vjp(
                        lambda bp, sp: last_fn(bp, sp, pay, labels, mask,
                                               flags_c),
                        vary(blocks_c), vary(shared))
                    gb_c, gs = vjp(to_varying(jnp.ones((), jnp.float32)))
                    carry = _add_block_grads(carry, gb_c)
                    return _add_shared_grads(carry, gs), zero_pay

                branches += [br_bwdi_mid, br_bwdi_first, br_bwdi_last,
                             br_w_mid, br_w_first, br_w_last]

            if remat:
                # ---- explicit recompute: the R tick hands the boundary
                # checkpoint from the act ring to the remat ring (the
                # replay FLOPs fuse into the B tick's vjp — see module
                # docstring).  RCP_FIRST rows carry slot -1 and stash
                # nothing (their block input is the token batch).
                def br_rcp(carry):
                    cur = jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(a, grm, 0,
                                                               False),
                        carry["rmt"])
                    val = jax.tree.map(
                        lambda new, old: jnp.where(row[13] >= 0, new, old),
                        act_in, cur)
                    rmt = jax.tree.map(
                        lambda buf, p: jax.lax.dynamic_update_index_in_dim(
                            buf, p, grm, 0), carry["rmt"], val)
                    return dict(carry, rmt=rmt), zero_pay

                while len(branches) < RCP_MID:
                    branches.append(br_idle)      # unused op-code slots
                branches += [br_rcp, br_rcp, br_rcp]

            carry, out = jax.lax.switch(op, branches, carry)

            # ---- route ----
            # per-channel delivery: the producer's send code picks the
            # physical route (down / up / wrap / local ppermute), the
            # consumer's recv columns (rows 6-11) say which queue slot
            # each channel's arrival lands in.  Wrap arrivals reuse the
            # down (F @ device 0) / up (B @ device P-1) columns, which
            # those devices cannot otherwise receive on.  Channels a
            # table never uses are compiled out (static booleans).
            def sel(code):
                return jax.tree.map(
                    lambda a: jnp.where(snd == code, a,
                                        jnp.zeros_like(a)), out)
            perm_dn = [(i, i + 1) for i in range(P_ - 1)]
            perm_up = [(i + 1, i) for i in range(P_ - 1)]
            perm_h = ([(P_ - 1, 0), (0, P_ - 1)] if P_ > 1 else [(0, 0)])
            moved_h = None
            if use_hop:
                hop_pay = jax.tree.map(lambda a, b: a + b,
                                       sel(SEND_HOPF), sel(SEND_HOPB))
                moved_h = _ppermute(hop_pay, pp, perm_h)

            def q_write(q, slot, val):
                cur = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, jnp.maximum(slot, 0), 0, False), q)
                val = jax.tree.map(
                    lambda new, old: jnp.where(slot >= 0, new, old),
                    val, cur)
                return jax.tree.map(
                    lambda a, vv: jax.lax.dynamic_update_index_in_dim(
                        a, vv, jnp.maximum(slot, 0), 0), q, val)

            fq, bq = carry["fq"], carry["bq"]
            if use_f_dn or use_hop:
                arr = _ppermute(sel(SEND_FWD), pp, perm_dn) if use_f_dn \
                    else jax.tree.map(jnp.zeros_like, zero_pay)
                if use_hop:
                    arr = jax.tree.map(
                        lambda a, b: jnp.where(s_idx == 0, b, a),
                        arr, moved_h)
                fq = q_write(fq, row[6], arr)
            if use_f_up:
                fq = q_write(fq, row[7],
                             _ppermute(sel(SEND_F_UP), pp, perm_up))
            if use_f_loc:
                fq = q_write(fq, row[8], sel(SEND_F_LOC))
            if use_b_up or use_hop:
                arr = _ppermute(sel(SEND_BWD), pp, perm_up) if use_b_up \
                    else jax.tree.map(jnp.zeros_like, zero_pay)
                if use_hop:
                    arr = jax.tree.map(
                        lambda a, b: jnp.where(s_idx == P_ - 1, b, a),
                        arr, moved_h)
                bq = q_write(bq, row[10], arr)
            if use_b_dn:
                bq = q_write(bq, row[9],
                             _ppermute(sel(SEND_B_DOWN), pp, perm_dn))
            if use_b_loc:
                bq = q_write(bq, row[11], sel(SEND_B_LOC))

            carry = dict(carry, fq=pin_buf(fq), bq=pin_buf(bq),
                         act=pin_buf(carry["act"]))
            if split:
                carry = dict(carry, wx=pin_buf(carry["wx"]),
                             wdy=pin_buf(carry["wdy"]))
            if remat:
                carry = dict(carry, rmt=pin_buf(carry["rmt"]))
            return carry, None

        init = jax.tree.map(to_varying, carry_init())
        carry, _ = jax.lax.scan(tick, init, jnp.arange(tab.T))

        # gradients: block grads stay stage-local; shared grads psum over pp
        gb = [jax.tree.map(lambda a: a[None], t) for t in carry["gb"]]
        gs = jax.tree.map(lambda a: jax.lax.psum(a, pp), carry["gs"])
        loss = jax.lax.psum(carry["loss"], pp)
        n = jax.lax.psum(carry["nloss"], pp)
        metrics = {"loss": loss / jnp.maximum(n, 1.0), "n_microbatches": n}
        return {"blocks": gb, **{k: gs[k] for k in gs}}, metrics

    def call(params, batch):
        in_specs = (
            P(pp),
            {"blocks": [jax.tree.map(lambda _: P(pp), t) for t in
                        params["blocks"]],
             **{k: jax.tree.map(lambda _: P(), params[k])
                for k in params if k != "blocks"}},
            jax.tree.map(lambda _: P(), batch),
        )
        out_specs = (
            {"blocks": [jax.tree.map(lambda _: P(pp), t) for t in
                        params["blocks"]],
             **{k: jax.tree.map(lambda _: P(), params[k])
                for k in params if k != "blocks"}},
            {"loss": P(), "n_microbatches": P()},
        )
        stage_iota = jnp.arange(tab.P, dtype=jnp.int32)
        return jax.shard_map(spmd, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs,
                             axis_names={pp},
                             check_vma=check_vma(kernels_on))(
            stage_iota, params, batch)
    return call


# ---------------------------------------------------------------------------
# phase-compiled executor
# ---------------------------------------------------------------------------

def _payload_struct(spec: PipelineSpec,
                    S: Optional[int] = None) -> List[Tuple[str,
                                                           Tuple[int, ...],
                                                           Any]]:
    """(key, shape, dtype) of every boundary-payload leaf, in wire
    order.  The phase executor stores payloads *byte-packed*: one
    ``uint16 [mbB, W]`` row-block per payload, so every ring buffer,
    queue and collective moves a single array instead of a tree.
    ``S`` overrides the sequence length (the seqpipe executor packs
    1/n_seq-size sequence-chunk boundaries)."""
    dtype = jnp.dtype(spec.cfg.compute_dtype)
    S = spec.S if S is None else S
    entries = [("x", (spec.mbB, S, spec.cfg.d_model), dtype),
               ("aux", (1,), jnp.dtype(jnp.float32))]
    if spec.enc_len:
        entries.append(("enc", (spec.mbB, spec.enc_len, spec.cfg.d_model),
                        dtype))
    return entries


def _wire_of(spec: PipelineSpec) -> str:
    return getattr(spec, "wire", "fp32")


def _leaf_exact(key: str, dt, wire: str) -> bool:
    """True when this payload leaf travels as an exact bitcast: the
    fp32 wire always, the ``aux`` scalar always (it is a loss term —
    never quantized), and 16-bit compute dtypes on the bf16 wire (the
    cast would be the identity)."""
    return (key == "aux" or wire == "fp32"
            or (wire == "bf16" and jnp.dtype(dt).itemsize <= 2))


def _payload_words(spec: PipelineSpec, S: Optional[int] = None) -> int:
    """Packed row width (uint16 words per batch row) under the spec's
    wire dtype: exact leaves bitcast to ``itemsize/2`` words per
    element, bf16 leaves to one, int8 leaves to half a word per element
    plus two leading scale words per row."""
    w = 0
    wire = _wire_of(spec)
    B = spec.mbB
    for key, shape, dt in _payload_struct(spec, S):
        ws = jnp.dtype(dt).itemsize // 2
        if key == "aux":
            w += int(np.prod(shape)) * ws
        elif _leaf_exact(key, dt, wire):
            w += int(np.prod(shape)) * ws // B
        elif wire == "bf16":
            w += int(np.prod(shape)) // B
        else:                                   # int8
            elts = int(np.prod(shape)) // B
            assert elts % 2 == 0, "int8 wire needs an even row length"
            w += 2 + elts // 2
    return w


def _pack_payload(spec: PipelineSpec, pay: Dict[str, Any],
                  S: Optional[int] = None) -> jnp.ndarray:
    """Payload dict -> packed ``uint16 [mbB, W]``.  The batch axis stays
    leading so ring buffers remain dp-shardable; the batch-free ``aux``
    scalar is broadcast across rows and read back from row 0.

    Exact leaves (see :func:`_leaf_exact`) are a pure bitcast — the
    fp32 wire is bitwise.  The bf16 wire casts then bitcasts (one word
    per element); the int8 wire quantizes per row with a symmetric
    scale ``amax/127`` carried in two leading uint16 words (an fp32
    bitcast), element pairs bitcast into single words."""
    with jax.named_scope("wire"):
        B = spec.mbB
        wire = _wire_of(spec)
        parts = []
        for key, shape, dt in _payload_struct(spec, S):
            a = pay[key]
            if _leaf_exact(key, dt, wire):
                w = jax.lax.bitcast_convert_type(a, jnp.uint16)
                if key == "aux":
                    w = jnp.broadcast_to(w.reshape(1, -1), (B, w.size))
                else:
                    w = w.reshape(B, -1)
            elif wire == "bf16":
                w = jax.lax.bitcast_convert_type(
                    a.astype(jnp.bfloat16), jnp.uint16).reshape(B, -1)
            else:                                   # int8
                flat = a.reshape(B, -1).astype(jnp.float32)
                scale = jnp.maximum(jnp.max(jnp.abs(flat), axis=1,
                                            keepdims=True), 1e-30) / 127.0
                q = jnp.clip(jnp.round(flat / scale), -127, 127)
                qw = jax.lax.bitcast_convert_type(
                    q.astype(jnp.int8).reshape(B, -1, 2), jnp.uint16)
                sw = jax.lax.bitcast_convert_type(scale, jnp.uint16)
                w = jnp.concatenate([sw.reshape(B, 2), qw], axis=1)
            parts.append(w)
        return parts[0] if len(parts) == 1 \
            else jnp.concatenate(parts, axis=1)


def _unpack_payload(spec: PipelineSpec, flat: jnp.ndarray,
                    S: Optional[int] = None) -> Dict[str, Any]:
    """Inverse of :func:`_pack_payload` — bitwise for exact leaves,
    dequantizing for compressed ones.  Forward and backward branches
    both read the *stored wire bytes*, so the chunk pullback linearizes
    at exactly the (dequantized) primal point the forward consumed."""
    with jax.named_scope("wire"):
        B = spec.mbB
        wire = _wire_of(spec)
        out: Dict[str, Any] = {}
        off = 0
        for key, shape, dt in _payload_struct(spec, S):
            ws = jnp.dtype(dt).itemsize // 2
            if key == "aux":
                n = int(np.prod(shape)) * ws
                seg = flat[0:1, off:off + n]
                out[key] = jax.lax.bitcast_convert_type(
                    seg.reshape(shape + ((ws,) if ws > 1 else ())), dt)
            elif _leaf_exact(key, dt, wire):
                n = int(np.prod(shape)) * ws // B
                seg = flat[:, off:off + n]
                out[key] = jax.lax.bitcast_convert_type(
                    seg.reshape(shape + ((ws,) if ws > 1 else ())), dt)
            elif wire == "bf16":
                n = int(np.prod(shape)) // B
                seg = flat[:, off:off + n]
                out[key] = jax.lax.bitcast_convert_type(
                    seg, jnp.bfloat16).reshape(shape).astype(dt)
            else:                                   # int8
                elts = int(np.prod(shape)) // B
                n = 2 + elts // 2
                seg = flat[:, off:off + n]
                scale = jax.lax.bitcast_convert_type(
                    seg[:, 0:2].reshape(B, 1, 2), jnp.float32)
                q = jax.lax.bitcast_convert_type(seg[:, 2:], jnp.int8)
                x = q.astype(jnp.float32).reshape(B, elts) * scale
                out[key] = x.reshape(shape).astype(dt)
            off += n
    return out


def _traced_once(fn):
    """Wrap ``fn`` so its Python body is traced once per distinct
    argument signature: an inner ``jax.jit`` caches the trace, and every
    later call, including the ``jax.vjp`` of a backward branch, reuses
    the recorded jaxpr (differentiation transforms the jaxpr and does
    not re-run Python).  XLA inlines the call, so the compiled program
    is the same as for a direct call."""
    return jax.jit(fn)


def _make_train_grads_phase(spec: PipelineSpec, mesh, ocfg=None,
                            opt_m=None):
    """The phase-compiled executor (see :func:`make_train_grads_fn`).

    With ``ocfg``/``opt_m`` set (see :func:`make_train_update_fn`) the
    AdamW update runs *inside* the shard_map region after the tick scan
    — no separate optimizer phase — and ``call`` becomes
    ``(params, opt_state, batch) -> (params, opt_state, metrics)``.

    Three structural changes versus the legacy per-tick interpreter:

    1. **Unified op branches, traced once.**  The first/mid/last x
       {F, B, W} branch triplicates collapse into one masked forward
       body: the block input is ``select(is_first, embed(tokens),
       wire_payload)`` and the loss head runs unconditionally with its
       cotangent seeded ``select(is_last, 1, 0)``.  Selects and
       zero-cotangent pullbacks are exact, so gradients are unchanged
       (the cross-schedule pairs stay bitwise where they were bitwise).
       The body is wrapped in an inner ``jax.jit``, so its Python trace
       runs exactly once per executor — every branch (forward, and the
       B/W/input-grad branches through ``jax.vjp``) reuses the cached
       jaxpr.  The ``lax.switch`` then has at most 5 branches
       (idle/F/B/W/R), pruned per phase to the op codes its rows use.
    2. **Phase segmentation.**  :func:`~repro.core.tasktable
       .factor_phases` factors ``[T, P]`` into warmup + a steady-state
       period replayed with a per-period microbatch stride + cooldown;
       the hot scan runs over the compressed periodic op-stream (the
       compiled program becomes independent of ``m`` once the steady
       state covers the extra microbatches), and warmup/cooldown scans
       carry only their own op codes and routes.  FIFO ring slots are
       re-derived from ``mb`` on device (:func:`~repro.core.tasktable
       .derive_slots`), which is what lets the steady period be one
       microbatch's footprint rather than the lcm of the ring depths.
    3. **Collective batching.**  Payloads travel byte-packed
       (:func:`_pack_payload`), hop wraps fold into full ring rotations
       (the table already lands wrap arrivals on the edge devices'
       dn/up recv columns), and same-direction F/B payloads stack into
       one ``ppermute`` — at most two collectives per tick, zero on
       device-local routes.  Queue writes use a trash slot (one spare
       row per ring) instead of the read-modify-write select pair.
    """
    cfg = spec.cfg
    tab = spec.table
    P_, v = tab.P, tab.v
    pp = spec.pp_axis
    kernels_on = get_backend(spec.kernels).fuses
    plan = factor_phases(tab)
    A = tab.arrays()                               # [T, P, 16]
    stream = replay_phases(tab, plan)
    assert np.array_equal(stream, A), \
        "phase factorization is not a pure re-encoding of the table"
    # the one-tick-shifted row stream of the deferred route: tick t
    # routes tick t-1's payload with tick t-1's columns (tick 0 routes
    # nothing — null send code, trash recv slots)
    null_row = np.zeros((1, tab.P, 16), np.int32)
    null_row[..., 3:] = -1
    null_row[..., 5] = SEND_NONE
    null_row[..., 14] = 0
    prev_stream = np.concatenate([null_row, stream[:-1]], axis=0)

    split, remat = tab.has_w, tab.has_r
    if spec.grad_psum_bits:
        assert ocfg is None, \
            "compressed gradient psum composes with the grads fn only " \
            "(the fused-optimizer path keeps the exact psum)"

    def ring_offsets(depths: Dict[int, int]):
        off = np.zeros(v, np.int64)
        total = 0
        for c in range(v):
            off[c] = total
            total += depths.get(c, 0)
        return jnp.asarray(off), total

    act_offsets, total_act = ring_offsets(tab.act_depth)
    w_offsets, total_w = ring_offsets(tab.wstash_depth)
    r_offsets, total_rmt = ring_offsets(tab.rmt_depth)
    flags_np = spec.layout.flags(cfg)
    Wb = _payload_words(spec)
    counts = {"embed": 0, "chunk": 0, "head": 0}

    def spmd(stage_iota, params, batch, opt_state=None, psum_ef=None):
        s_idx = stage_iota[0]
        blocks = [jax.tree.map(lambda a: a[0], t) for t in params["blocks"]]
        flags = {k: jnp.asarray(vv)[s_idx] for k, vv in flags_np.items()}
        shared = {k: params[k] for k in params if k != "blocks"}

        def to_varying(a):
            # varying over pp like the stage-sharded inputs (a no-op in a
            # region that interprets kernels, see kernels.check_vma)
            return match_vma(a, stage_iota)

        def vary(x):
            return jax.tree.map(to_varying, x)

        # every stage reads its own copy of the shared (embed / head /
        # norm) parameters, so the traced-once bodies below see one
        # argument signature; their gradients are psum'd after the scan
        shared = vary(shared)

        # ---- unified forward body: traced ONCE, reused by every branch
        # directly or through jax.vjp.  The chunk body is its own
        # traced-once core — the hot mid-position backward branches
        # differentiate it directly, exactly like the legacy mid
        # branches — and the full body wraps it with the embed
        # (is_first) and loss head (is_last) inside ``lax.cond``, so
        # mid ticks skip their compute at runtime.  Cond transposes to
        # cond, whose untaken side contributes exact zeros — gradients
        # match the separate first/mid/last branches bitwise. ----
        def chunk_core(blocks_c, pay, flags_c):
            counts["chunk"] += 1
            return vary(_chunk_fwd(spec, blocks_c, flags_c, pay))

        def embed_core(shared_p, tok, patch, frames):
            counts["embed"] += 1
            with jax.named_scope("embed"):
                return vary(_embed_tokens(spec, shared_p, tok, patch,
                                          frames))

        def head_core(pay_out, shared_p, labels, mask):
            counts["head"] += 1
            with jax.named_scope("head_loss"):
                return to_varying(_head_loss(spec, shared_p, pay_out,
                                             labels, mask))

        jchunk = _traced_once(chunk_core)
        jembed = _traced_once(embed_core)
        jhead = _traced_once(head_core)

        def fwd_core(blocks_c, shared_p, pay, tok, patch, frames, labels,
                     mask, flags_c, is_first, is_last):
            pay = jax.lax.cond(
                is_first,
                lambda _: jembed(shared_p, tok, patch, frames),
                lambda _: vary(dict(pay)), None)
            out = jchunk(blocks_c, pay, flags_c)
            ce = jax.lax.cond(
                is_last,
                lambda _: jhead(dict(out), shared_p, labels, mask),
                lambda _: to_varying(jnp.zeros((), jnp.float32)), None)
            return vary(out), to_varying(ce)

        jcore = _traced_once(fwd_core)

        def zero_gs():
            with jax.named_scope("grad_accum"):
                return vary(jax.tree.map(
                    lambda a: jnp.zeros(a.shape, jnp.float32), shared))

        with jax.named_scope("wire"):
            zero_wire = to_varying(jnp.zeros((spec.mbB, Wb), jnp.uint16))
        with jax.named_scope("grad_accum"):
            zero_blocks_g = jax.tree.map(jnp.zeros_like, blocks)

        def pin_buf(a):
            """Packed rings are [slots, mbB, W]: batch over dp."""
            if a.ndim >= 3:
                return shard(a, None, "dp", None)
            return a

        def ring(slots, trash):
            with jax.named_scope("ring"):
                return pin_buf(jnp.zeros((slots + (1 if trash else 0),
                                          spec.mbB, Wb), jnp.uint16))

        def carry_init():
            carry = {
                "fq": ring(tab.fq_depth, True),
                "bq": ring(tab.bq_depth, True),
                "act": ring(total_act, True),
                "gb": zero_blocks_g,
                "gs": zero_gs(),
                "loss": jnp.zeros((), jnp.float32),
                "nloss": jnp.zeros((), jnp.float32),
            }
            if split:
                carry["wx"] = ring(total_w, True)
                carry["wdy"] = ring(total_w, True)
            if remat:
                carry["rmt"] = ring(total_rmt, True)
            return carry

        def rd(buf, i):
            with jax.named_scope("ring"):
                return jax.lax.dynamic_index_in_dim(buf, i, 0,
                                                    keepdims=False)

        def wr(buf, val, i):
            with jax.named_scope("ring"):
                return jax.lax.dynamic_update_index_in_dim(buf, val, i, 0)

        def tick_core(carry, row_all, codes):
            row = row_all[s_idx]                   # [16]
            op, c = row[0], row[1]
            mb, src = row[2], row[3]
            aslot = row[4]
            gact = jnp.where(aslot < 0, total_act,
                             act_offsets[c] + jnp.maximum(aslot, 0))
            gw = (w_offsets[c] + jnp.maximum(row[12], 0)) if split \
                else None
            rslot = row[13]
            grm = jnp.where(rslot < 0, total_rmt,
                            r_offsets[c] + jnp.maximum(rslot, 0)) \
                if remat else None

            def blocks_at():
                with jax.named_scope("ring"):
                    blocks_c = [jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(a, c, 0,
                                                               False),
                        t_) for t_ in blocks]
                    flags_c = {k: jax.lax.dynamic_index_in_dim(vv, c, 0,
                                                               False)
                               for k, vv in flags.items()}
                return blocks_c, flags_c

            def batch_inputs():
                tokens = rd(batch["tokens"], mb)
                tok_in, labels = tokens[:, :-1], tokens[:, 1:]
                patch = (rd(batch["patch_embeds"], mb)
                         if "patch_embeds" in batch else None)
                frames = (rd(batch["frame_embeds"], mb)
                          if "frame_embeds" in batch else None)
                mask = (rd(batch["loss_mask"], mb)
                        if "loss_mask" in batch else None)
                return tok_in, patch, frames, labels, mask

            def bnd_read(carry):
                with jax.named_scope("ring"):
                    a = rd(carry["act"], gact)
                    if remat:
                        a = jnp.where(rslot >= 0, rd(carry["rmt"], grm), a)
                    return a

            def masked_dy(dy_pk, is_last):
                with jax.named_scope("wire"):
                    dy = _unpack_payload(spec, dy_pk)
                    return jax.tree.map(
                        lambda a: jnp.where(is_last, jnp.zeros_like(a), a),
                        dy)

            # ---- branches are PURE PRODUCERS: they read the carry's
            # ring buffers (conditional inputs alias freely) but every
            # state write — rings, gradient accumulators, loss — happens
            # AFTER the switch.  XLA conditionals copy every carry
            # element they return (pass-through included), so threading
            # multi-MB gradient accumulators through the switch would
            # pay a full copy per non-idle tick; pure branches return
            # only their tick-sized products: (wire_out, gb_delta,
            # gs_delta, ce, n_loss, stash_a[, stash_b]), with exact
            # zeros where a branch has nothing to contribute.  Ring
            # writes then run unconditionally (trash slots absorb the
            # inactive classes); the accumulator adds are the one
            # exception, ``lax.cond``-gated on the op class below —
            # see the comment at the gb/gs update. ----
            def zeros_gbd():
                with jax.named_scope("grad_accum"):
                    return [vary(jax.tree.map(
                        lambda a: jnp.zeros(a.shape[1:], a.dtype), t))
                        for t in zero_blocks_g]

            def gs_of(gs_raw):
                return jax.tree.map(lambda z, g: g.astype(z.dtype),
                                    zero_gs(), gs_raw)

            z32 = to_varying(jnp.zeros((), jnp.float32))

            def ret(out=None, gbd=None, gsd=None, ce=None, nl=None,
                    st_a=None, st_b=None):
                r = (out if out is not None else zero_wire,
                     gbd if gbd is not None else zeros_gbd(),
                     gsd if gsd is not None else zero_gs(),
                     ce if ce is not None else z32,
                     nl if nl is not None else z32,
                     st_a if st_a is not None else zero_wire)
                if split:
                    r += (st_b if st_b is not None else zero_wire,)
                return r

            def br_idle(_):
                return ret()

            def br_fwd(_):
                is_first = op == FWD_FIRST
                is_last = op == FWD_LAST
                blocks_c, flags_c = blocks_at()
                tok, patch, frames, labels, mask = batch_inputs()
                pin = rd(carry["fq"], jnp.maximum(src, 0))
                pay = _unpack_payload(spec, pin)
                with jax.named_scope("fwd"):
                    out, ce = jcore(blocks_c, shared, pay, tok, patch,
                                    frames, labels, mask, flags_c,
                                    is_first, is_last)
                return ret(out=_pack_payload(spec, out), ce=ce,
                           nl=jnp.where(is_last, 1.0, 0.0), st_a=pin)

            def br_bwd(_):               # fused backward, all positions:
                # one chunk-pullback body; the head (is_last) and embed
                # (is_first) pullbacks chain around it inside lax.cond —
                # the same composition reverse-mode AD performs inside a
                # monolithic vjp, so gradients are unchanged, but the
                # mid-position hot path executes the bare chunk vjp only
                is_first = op == BWD_FIRST
                is_last = op == BWD_LAST
                blocks_c, flags_c = blocks_at()
                tok, patch, frames, labels, mask = batch_inputs()
                bnd = bnd_read(carry)
                with jax.named_scope("replay"):
                    pay_in = jax.lax.cond(
                        is_first,
                        lambda _: jembed(shared, tok, patch, frames),
                        lambda _: vary(_unpack_payload(spec, bnd)), None)
                    out, vjp = jax.vjp(
                        lambda bp, pay: jchunk(bp, pay, flags_c),
                        vary(blocks_c), vary(pay_in))
                qdy = _unpack_payload(spec,
                                      rd(carry["bq"], jnp.maximum(src, 0)))

                def head_pull(_):
                    with jax.named_scope("replay"):
                        _, hvjp = jax.vjp(
                            lambda po, sp: jhead(po, sp, labels, mask),
                            vary(dict(out)), vary(shared))
                    dy, gs = hvjp(to_varying(jnp.ones((), jnp.float32)))
                    return dy, gs_of(gs)

                with jax.named_scope("bwd"):
                    dy, gs = jax.lax.cond(
                        is_last, head_pull,
                        lambda _: (vary(dict(qdy)), zero_gs()), None)
                    gb_c, dx = vjp(dy)

                def embed_pull(_):
                    with jax.named_scope("replay"):
                        _, evjp = jax.vjp(
                            lambda sp: jembed(sp, tok, patch, frames),
                            vary(shared))
                    (gs_e,) = evjp(vary(dict(dx)))
                    return gs_of(gs_e)

                with jax.named_scope("bwd"):
                    gs = jax.tree.map(
                        lambda a, b: a + b, gs,
                        jax.lax.cond(is_first, embed_pull,
                                     lambda _: zero_gs(), None))
                return ret(out=_pack_payload(spec, dx), gbd=gb_c,
                           gsd=gs_of(gs))

            def br_bwdi_mid(_):          # split backward, mid position:
                # payload-only diff of the bare chunk body + stash
                blocks_c, flags_c = blocks_at()
                bnd = bnd_read(carry)
                dy_pk = rd(carry["bq"], jnp.maximum(src, 0))
                dy = _unpack_payload(spec, dy_pk)
                pay = _unpack_payload(spec, bnd)
                with jax.named_scope("replay"):
                    _, vjp = jax.vjp(
                        lambda pay: jchunk(vary(blocks_c), pay, flags_c),
                        vary(pay))
                with jax.named_scope("bwd"):
                    (dx,) = vjp(vary(dy))
                return ret(out=_pack_payload(spec, dx), st_a=bnd,
                           st_b=dy_pk)

            def br_bwdi(_):              # split backward, first/last:
                # input grad + stash through the full unified body
                is_first = op == BWD_FIRST
                is_last = op == BWD_LAST
                blocks_c, flags_c = blocks_at()
                tok, patch, frames, labels, mask = batch_inputs()
                bnd = bnd_read(carry)
                dy_pk = rd(carry["bq"], jnp.maximum(src, 0))
                dy = masked_dy(dy_pk, is_last)
                seed = jnp.where(is_last, 1.0, 0.0)
                pay = _unpack_payload(spec, bnd)
                with jax.named_scope("replay"):
                    _, vjp = jax.vjp(
                        lambda pay: jcore(vary(blocks_c), vary(shared),
                                          pay, tok, patch, frames, labels,
                                          mask, flags_c, is_first,
                                          is_last),
                        vary(pay))
                with jax.named_scope("bwd"):
                    (dx,) = vjp((vary(dy), to_varying(seed)))
                return ret(out=_pack_payload(spec, dx), st_a=bnd,
                           st_b=dy_pk)

            def br_w_mid(_):             # split weight grad, mid: like
                # the legacy mid branch, blocks-only differentiation of
                # the bare chunk body
                blocks_c, flags_c = blocks_at()
                pay = _unpack_payload(spec, rd(carry["wx"], gw))
                dy = _unpack_payload(spec, rd(carry["wdy"], gw))
                with jax.named_scope("replay"):
                    _, vjp = jax.vjp(
                        lambda bp: jchunk(bp, vary(pay), flags_c),
                        vary(blocks_c))
                with jax.named_scope("bwd"):
                    (gb_c,) = vjp(vary(dy))
                return ret(gbd=gb_c)

            def br_w_edge(_):            # split weight grad, first/last
                is_first = op == WGT_FIRST
                is_last = op == WGT_LAST
                blocks_c, flags_c = blocks_at()
                tok, patch, frames, labels, mask = batch_inputs()
                pay = _unpack_payload(spec, rd(carry["wx"], gw))
                dy = masked_dy(rd(carry["wdy"], gw), is_last)
                seed = jnp.where(is_last, 1.0, 0.0)
                with jax.named_scope("replay"):
                    _, vjp = jax.vjp(
                        lambda bp, sp: jcore(bp, sp, vary(pay), tok, patch,
                                             frames, labels, mask, flags_c,
                                             is_first, is_last),
                        vary(blocks_c), vary(shared))
                with jax.named_scope("bwd"):
                    gb_c, gs = vjp((vary(dy), to_varying(seed)))
                return ret(gbd=gb_c, gsd=gs_of(gs))

            def br_rcp(_):               # hand act checkpoint -> remat
                return ret(st_a=rd(carry["act"], gact))

            if split:
                groups = ((IDLE,), F_OPS,
                          (BWD_MID,), (BWD_FIRST, BWD_LAST),
                          (WGT_MID,), (WGT_FIRST, WGT_LAST), R_OPS)
                builders = (br_idle, br_fwd, br_bwdi_mid, br_bwdi,
                            br_w_mid, br_w_edge, br_rcp)
            else:
                groups = ((IDLE,), F_OPS, B_OPS, R_OPS)
                builders = (br_idle, br_fwd, br_bwd, br_rcp)
            remap = np.zeros(13, np.int32)
            branches = []
            for ops, fn in zip(groups, builders):
                if any(cd in codes for cd in ops):
                    for cd in ops:
                        remap[cd] = len(branches)
                    branches.append(fn)
            if len(branches) == 1:
                res = branches[0](())
            else:
                res = jax.lax.switch(jnp.asarray(remap)[op], branches, ())
            out, gb_d, gs_d, ce, nl, st_a = res[:6]
            st_b = res[6] if split else None

            # ---- unconditional state writes (trash slots swallow the
            # inactive op classes; slice updates stay in place) ----
            is_f = (op >= FWD_MID) & (op <= FWD_LAST)
            carry = dict(carry, act=wr(
                carry["act"], st_a, jnp.where(is_f, gact, total_act)))
            if split:
                is_b = (op >= BWD_MID) & (op <= BWD_LAST)
                ws = jnp.where(is_b, gw, total_w)
                carry = dict(carry, wx=wr(carry["wx"], st_a, ws),
                             wdy=wr(carry["wdy"], st_b, ws))
            if remat:
                is_r = op >= RCP_MID
                carry = dict(carry, rmt=wr(
                    carry["rmt"], st_a, jnp.where(is_r, grm, total_rmt)))
            # Gradient accumulators: only B/W ops ever produce nonzero
            # deltas (F/R/idle branches return exact zeros), so the
            # chunk-slice read-add-write on ``gb`` and the full-tree add
            # on ``gs`` are gated on the op class.  This is what keeps
            # the overlap table's skew ticks cheap: the stretched table
            # has many more non-B/W ticks, and unconditionally adding
            # zeros would pay the full accumulator memory traffic on
            # every one of them.
            is_g = (op >= BWD_MID) & (op <= WGT_LAST)
            with jax.named_scope("grad_accum"):
                gb = jax.lax.cond(
                    is_g,
                    lambda t: [jax.tree.map(
                        lambda g, d: jax.lax.dynamic_update_index_in_dim(
                            g, jax.lax.dynamic_index_in_dim(g, c, 0, False)
                            + d, c, 0), gt, dt)
                        for gt, dt in zip(t, gb_d)],
                    lambda t: list(t), carry["gb"])
            is_gs = ((op == BWD_FIRST) | (op == BWD_LAST)
                     | (op == WGT_FIRST) | (op == WGT_LAST))
            with jax.named_scope("grad_accum"):
                gs = jax.lax.cond(
                    is_gs,
                    lambda t: jax.tree.map(lambda a, b: a + b, t, gs_d),
                    lambda t: t, carry["gs"])
                carry = dict(carry, gb=gb, gs=gs,
                             loss=carry["loss"] + ce,
                             nloss=carry["nloss"] + nl)
            return carry, out, row

        # ---- route: the shared wire protocol (:func:`_build_route`) —
        # rotation pair above :data:`EXCHANGE_AG_MAX` all-gathered bytes
        # per tick, single-collective exchange below it.  The table's
        # static send-code set compiles unused routes away.
        codes = tuple(int(x) for x in np.unique(A[:, :, 0]))
        snds = frozenset(int(x) for x in np.unique(A[:, :, 5]))
        use_ag = P_ * spec.mbB * Wb * 2 <= _exchange_ag_max()

        def make_tick():
            route_x, route_l = _build_route(tab, P_, pp, snds, use_ag,
                                            s_idx)
            defer = tab.overlap and route_x.has_xdev
            xdev_have = [cd for cd in snds if cd not in LOCAL_SENDS]

            def skip_quiet(route_row_all, fq, bq, payload):
                # Quiet ticks (no device holds a cross-device send code —
                # the row is replicated table data, so the predicate is
                # SPMD-uniform) skip the collective rendezvous entirely.
                # The overlap table's stretched steady state has several
                # of these per period; on a latency-bound wire they are
                # pure fixed cost.
                if not xdev_have:
                    return fq, bq
                with jax.named_scope("wire"):
                    anyx = jnp.any(functools.reduce(
                        jnp.logical_or,
                        [route_row_all[:, 5] == cd for cd in xdev_have]))
                    return jax.lax.cond(
                        anyx,
                        lambda a: route_x(a[0], a[1], a[2], route_row_all,
                                          route_row_all[s_idx]),
                        lambda a: (a[0], a[1]), (fq, bq, payload))

            def repin(carry):
                carry = dict(carry, act=pin_buf(carry["act"]))
                if split:
                    carry = dict(carry, wx=pin_buf(carry["wx"]),
                                 wdy=pin_buf(carry["wdy"]))
                if remat:
                    carry = dict(carry, rmt=pin_buf(carry["rmt"]))
                return carry

            if not defer:
                def tick(carry, rows):
                    row_all, _ = rows
                    carry, out, row = tick_core(carry, row_all, codes)
                    fq, bq = skip_quiet(row_all, carry["fq"],
                                        carry["bq"], out)
                    fq, bq = route_l(fq, bq, out, row)
                    return repin(dict(carry, fq=pin_buf(fq),
                                      bq=pin_buf(bq)))
                return tick, False

            # double-buffered exchange: this tick's collective delivers
            # the payload produced LAST tick (carry["wire"]) using last
            # tick's routing row — the collective shares no dataflow
            # with tick_core (which reads the pre-delivery queues), so
            # XLA is free to run it concurrently with the compute.  The
            # table's 2-tick cross-device gap (tasktable overlap mode)
            # guarantees no consumer needs the payload any earlier;
            # local channels keep same-tick delivery (1-tick gap).
            def tick(carry, rows):
                row_all, prow_all = rows
                fq, bq = skip_quiet(prow_all, carry["fq"],
                                    carry["bq"], carry["wire"])
                carry, out, row = tick_core(carry, row_all, codes)
                fq, bq = route_l(fq, bq, out, row)
                return repin(dict(carry, fq=pin_buf(fq),
                                  bq=pin_buf(bq), wire=out))

            return tick, True

        # ---- the op stream: the factored plan replayed tick-for-tick
        # (warmup rows, the steady-state period template advanced by its
        # per-period mb stride, cooldown rows, modular ring slots
        # re-derived per tick) — replay_phases() is asserted above to be
        # a pure re-encoding of the table, so the executor literally
        # consumes the factorization.  One scan, one compiled tick body.
        # The deferred route additionally scans over the stream shifted
        # by one tick (a null first row), giving each tick its
        # predecessor's routing columns.
        tick, defer = make_tick()
        carry0 = carry_init()
        if defer:
            carry0["wire"] = jnp.zeros((spec.mbB, Wb), jnp.uint16)
        carry, _ = jax.lax.scan(
            lambda cr, rw: (tick(cr, rw), None),
            vary(carry0), (jnp.asarray(stream), jnp.asarray(prev_stream)))

        if spec.grad_psum_bits:
            from repro.optim.compression import compressed_psum
            ef_local = jax.tree.map(lambda a: a[0], psum_ef)
            with jax.named_scope("grad_psum"):
                gs, new_ef = compressed_psum(carry["gs"], pp, ef_local,
                                             bits=spec.grad_psum_bits)
            new_ef = jax.tree.map(lambda a: a[None], new_ef)
        else:
            with jax.named_scope("grad_psum"):
                gs = jax.tree.map(lambda a: jax.lax.psum(a, pp),
                                  carry["gs"])
        with jax.named_scope("grad_psum"):
            loss = jax.lax.psum(carry["loss"], pp)
            n = jax.lax.psum(carry["nloss"], pp)
        metrics = {"loss": loss / jnp.maximum(n, 1.0), "n_microbatches": n}
        if ocfg is None:
            gb = [jax.tree.map(lambda a: a[None], t) for t in carry["gb"]]
            grads = {"blocks": gb, **{k: gs[k] for k in gs}}
            if spec.grad_psum_bits:
                return grads, metrics, new_ef
            return grads, metrics

        # ---- in-executor fused optimizer (make_train_update_fn): the
        # AdamW step runs here, inside the shard_map region, directly on
        # the stage-local block accumulators — no separate optimizer
        # phase outside the executor.  The math is identical to the
        # phase-separate astype(f32)/m -> adamw_update path: the only
        # cross-stage quantity is the clipping norm, reassembled exactly
        # via psum of the local block square-sums (per-leaf summation
        # order is unchanged, so the loss trajectory matches
        # step-count-exact). ----
        from repro.optim.adamw import adamw_update, cast_like

        def local_tree(t):
            return {"blocks": [jax.tree.map(lambda a: a[0], b)
                               for b in t["blocks"]],
                    **{k: t[k] for k in t if k != "blocks"}}

        def stack_tree(t):
            return {"blocks": [jax.tree.map(lambda a: a[None], b)
                               for b in t["blocks"]],
                    **{k: t[k] for k in t if k != "blocks"}}

        with jax.named_scope("optimizer"):
            g = jax.tree.map(lambda a: a.astype(jnp.float32) / opt_m,
                             {"blocks": carry["gb"], **{k: gs[k] for k in gs}})
            sq_b = sum(jnp.sum(jnp.square(a))
                       for a in jax.tree.leaves(g["blocks"]))
            sq_s = sum(jnp.sum(jnp.square(a)) for a in jax.tree.leaves(
                {k: g[k] for k in g if k != "blocks"}))
            gnorm = jnp.sqrt(jax.lax.psum(sq_b, pp) + sq_s + 1e-30)
            opt_local = {"step": opt_state["step"],
                         "mu": local_tree(opt_state["mu"]),
                         "nu": local_tree(opt_state["nu"]),
                         "master": local_tree(opt_state["master"])}
            master, new_opt, omet = adamw_update(g, opt_local, ocfg,
                                                 use_kernel=True,
                                                 grad_norm=gnorm)
            new_params = stack_tree(cast_like(
                master, {"blocks": blocks, **shared}))
        new_opt = {"step": new_opt["step"],
                   "mu": stack_tree(new_opt["mu"]),
                   "nu": stack_tree(new_opt["nu"]),
                   "master": stack_tree(new_opt["master"])}
        metrics = dict(metrics, grad_norm=omet["grad_norm"],
                       lr=omet["lr"])
        return new_params, new_opt, metrics

    def param_specs(tree):
        return {"blocks": [jax.tree.map(lambda _: P(pp), t) for t in
                           tree["blocks"]],
                **{k: jax.tree.map(lambda _: P(), tree[k])
                   for k in tree if k != "blocks"}}

    def call(params, batch):
        in_specs = (P(pp), param_specs(params),
                    jax.tree.map(lambda _: P(), batch))
        out_specs = (param_specs(params),
                     {"loss": P(), "n_microbatches": P()})

        stage_iota = jnp.arange(tab.P, dtype=jnp.int32)
        return jax.shard_map(spmd, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs,
                             axis_names={pp},
                             check_vma=check_vma(kernels_on))(
            stage_iota, params, batch)

    def call_ef(params, batch, psum_ef):
        """Grads fn with the compressed shared-gradient psum: the
        error-feedback residual is per-device state, stacked ``[P,
        ...]`` over the pipe axis exactly like the block leaves, and
        threaded through every step (see :func:`init_psum_ef`)."""
        ef_specs = jax.tree.map(lambda _: P(pp), psum_ef)
        in_specs = (P(pp), param_specs(params),
                    jax.tree.map(lambda _: P(), batch), ef_specs)
        out_specs = (param_specs(params),
                     {"loss": P(), "n_microbatches": P()}, ef_specs)

        stage_iota = jnp.arange(tab.P, dtype=jnp.int32)
        return jax.shard_map(
            lambda si, p, b, ef: spmd(si, p, b, psum_ef=ef), mesh=mesh,
            in_specs=in_specs, out_specs=out_specs,
            axis_names={pp}, check_vma=check_vma(kernels_on))(
            stage_iota, params, batch, psum_ef)

    def call_update(params, opt_state, batch):
        pspec = param_specs(params)
        ospec = {"step": P(), "mu": pspec, "nu": pspec, "master": pspec}
        in_specs = (P(pp), pspec, ospec,
                    jax.tree.map(lambda _: P(), batch))
        out_specs = (pspec, ospec,
                     {"loss": P(), "n_microbatches": P(),
                      "grad_norm": P(), "lr": P()})

        stage_iota = jnp.arange(tab.P, dtype=jnp.int32)
        return jax.shard_map(
            lambda si, p, o, b: spmd(si, p, b, o), mesh=mesh,
            in_specs=in_specs, out_specs=out_specs,
            axis_names={pp}, check_vma=check_vma(True))(
            stage_iota, params, opt_state, batch)

    if ocfg is not None:
        fn = call_update
    elif spec.grad_psum_bits:
        fn = call_ef
    else:
        fn = call
    fn.trace_counts = counts
    fn.phase_plan = plan
    return fn


def init_psum_ef(spec: PipelineSpec, params):
    """Zero error-feedback state for ``spec.grad_psum_bits``: one fp32
    residual per shared-parameter leaf, stacked ``[P, ...]`` over the
    pipe axis (each device carries its own residual).  Thread it
    through the grads fn: ``grads, metrics, ef = fn(params, batch,
    ef)``."""
    shared = {k: params[k] for k in params if k != "blocks"}
    return jax.tree.map(
        lambda a: jnp.zeros((spec.table.P,) + a.shape, jnp.float32),
        shared)


def _ppermute(x, axis, perm):
    """Tree-mapped ``lax.ppermute``; degenerate permutations (P=1 or any
    all-identity perm, e.g. the single-device hop wrap) skip the
    collective entirely and pass the payload through."""
    if all(s == d for s, d in perm):
        return x
    with jax.named_scope("exchange"):
        return jax.tree.map(lambda a: jax.lax.ppermute(a, axis, perm), x)
