"""Sequence-chunked SPMD pipeline executor.

Extends the lockstep tick executor of
:mod:`repro.core.pipeline_runtime` with the fifth scheduling coordinate:
every task processes one *sequence chunk* (``Sc = S / n_seq`` token
positions) of one microbatch, and two per-microbatch rings thread causal
attention across chunks:

- **KV-carry ring** (``carry["kv"]``, one slot per in-flight microbatch
  per layer-chunk): the statically-sized full-sequence K/V buffer of
  every layer the stage hosts.  Each F tick runs the chunk forward with
  the buffer as an attention *cache* at offset ``q * Sc`` — the
  positions below the offset hold the prefix written by earlier chunks,
  positions above the causal frontier are masked out (exactly zero
  probability), so chunked attention equals full-sequence attention
  row-for-row (see :mod:`repro.seqpipe.attention`).
- **dKV ring** (``carry["dkv"]``, same slots): the accumulated K/V
  cotangents.  Backwards run in *reverse* chunk order; each B tick
  replays its chunk's forward from the boundary payload + KV buffer
  inside ``jax.vjp`` and passes the ring content as the cotangent of
  the updated KV buffer.  The vjp then (a) routes the accumulated dK/dV
  of the chunk's *own* positions into the weight gradients, and (b)
  returns the cotangent w.r.t. the KV *input* — the prefix positions'
  accumulation plus this chunk's attention-to-prefix contribution —
  which is written back to the ring for the next (earlier) chunk.  The
  first backward of a microbatch (``q == n_seq-1``) seeds the cotangent
  with zeros, so no explicit ring zeroing is needed.

Loss accounting: each last-stage chunk computes the *partial* loss
``sum(nll [* mask] over its token slice) / D`` with D the
*whole-sequence* token count ``mbB * S`` (or the microbatch's total
mask count under ``batch["loss_mask"]``), so per-chunk losses (and
their gradient seeds) sum exactly to the unchunked microbatch mean —
chunked gradients match the unchunked pipeline up to float summation
order (``tests/helpers/split_fused_check.py --pair seq``, which also
runs masked).

Scope: dense-attention LMs (no SSM scan / encoder cross-attention / VLM
prefix / MoE aux losses — asserted by ``make_pipeline_spec``); fused
backward plus explicit-recompute ``R`` ticks (split-backward W is
IR/table-level only for now).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.pipeline_runtime import PipelineSpec, _embed_tokens
from repro.core.tasktable import (LOCAL_SENDS, SEND_BWD, SEND_FWD,
                                  SEND_HOPB, SEND_HOPF)
from repro.kernels import check_vma
from repro.models import backend as compute_backend
from repro.models import layers as L
from repro.models.backend import get_backend, head_loss
from repro.models.sharding import match_vma, shard


def _chunk_fwd_seq(spec: PipelineSpec, block_params_c, flags_c, payload,
                   kv, pos0):
    """Run this stage's layer chunk over one sequence chunk.

    ``kv``: {"k", "v"} with leaves [M, period, B, S, G, hd] — the
    microbatch's full-sequence KV buffer for every layer of the chunk.
    ``pos0``: traced absolute offset of the chunk's first position.
    Returns (payload_out, kv_out) with the chunk's K/V written at
    [pos0, pos0 + Sc)."""
    return compute_backend.chunk_fwd(spec, block_params_c, flags_c,
                                     payload, kv=kv, pos0=pos0)


def make_seq_train_grads_fn(spec: PipelineSpec, mesh,
                            executor: str = "phase"):
    """Seq-chunked counterpart of
    :func:`repro.core.pipeline_runtime.make_train_grads_fn` — same
    signature, same gradient semantics, 1/n_seq of the boundary-payload
    working set plus the KV-carry rings.  ``executor`` mirrors the core
    runtime: ``"phase"`` (phase-compiled; pure-producer branches,
    byte-packed sequence-chunk payloads, traced-once cores, single
    collective exchange) or ``"legacy"`` (the pre-phase per-tick
    interpreter, kept for A/B benchmarking)."""
    if executor == "phase":
        return _make_seq_train_grads_phase(spec, mesh)
    assert executor == "legacy", executor
    return _make_seq_train_grads_legacy(spec, mesh)


def _make_seq_train_grads_legacy(spec: PipelineSpec, mesh):
    cfg = spec.cfg
    tab = spec.table
    P_, v, ns = tab.P, tab.v, tab.n_seq
    assert ns > 1 and not tab.has_w
    assert tab.placement_name == "interleaved", \
        "seq-chunked executor supports the interleaved placement only"
    pp = spec.pp_axis
    kernels_on = get_backend(spec.kernels).fuses
    Sc = spec.S // ns
    table_arr = jnp.asarray(tab.arrays())              # [T, P, 16]

    def offsets(depths):
        off = np.zeros(v, np.int64)
        total = 0
        for c in range(v):
            off[c] = total
            total += depths.get(c, 0)
        return jnp.asarray(off), total

    act_offsets, total_act = offsets(tab.act_depth)
    kv_offsets, total_kv = offsets(tab.kv_depth)
    remat = tab.has_r
    r_offsets, total_rmt = offsets(tab.rmt_depth)
    flags_np = spec.layout.flags(cfg)
    M = spec.layout.M
    per = spec.layout.period
    G, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    def spmd(stage_iota, params, batch):
        s_idx = stage_iota[0]
        blocks = [jax.tree.map(lambda a: a[0], t) for t in params["blocks"]]
        flags = {k: jnp.asarray(vv)[s_idx] for k, vv in flags_np.items()}
        shared = {k: params[k] for k in params if k != "blocks"}
        dtype = jnp.dtype(cfg.compute_dtype)

        def to_varying(a):
            # varying over pp like the stage-sharded inputs (a no-op in a
            # region that interprets kernels, see kernels.check_vma)
            return match_vma(a, stage_iota)

        def vary(x):
            return jax.tree.map(to_varying, x)

        zero_pay = vary({"x": jnp.zeros((spec.mbB, Sc, cfg.d_model),
                                        dtype),
                         "aux": jnp.zeros((1,), jnp.float32)})
        zero_kv_slot = {"k": jnp.zeros((per, M, spec.mbB, spec.S, G, hd),
                                       dtype),
                        "v": jnp.zeros((per, M, spec.mbB, spec.S, G, hd),
                                       dtype)}
        # scan consumes leading M; store rings as [slots, M, per, ...]
        zero_kv_slot = jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1),
                                    zero_kv_slot)
        zero_blocks_g = jax.tree.map(jnp.zeros_like, blocks)
        zero_shared_g = jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), shared)

        def pin_buf(t):
            """Pin ring buffers batch-over-dp (payloads [slots, B, Sc, d]
            at axis 1; KV/dKV [slots, M, per, B, S, G, hd] at axis 3)."""
            def one(a):
                if a.ndim == 7:
                    return shard(a, None, None, None, "dp", None, None,
                                 None)
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
                "kv": pin_buf(jax.tree.map(
                    lambda a: jnp.zeros((total_kv,) + a.shape, a.dtype),
                    zero_kv_slot)),
                "dkv": pin_buf(jax.tree.map(
                    lambda a: jnp.zeros((total_kv,) + a.shape, a.dtype),
                    zero_kv_slot)),
                "gb": zero_blocks_g,
                "gs": zero_shared_g,
                "loss": jnp.zeros((), jnp.float32),
                "nloss": jnp.zeros((), jnp.float32),
            }
            if remat:
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
            # seq tables are interleaved-placement only: F payloads
            # arrive on the down channel, B payloads on the up channel
            rcf, rcb = row[6], row[10]
            q, kvslot = row[14], row[15]
            pos0 = q * Sc

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
            gkv = kv_offsets[c] + jnp.maximum(kvslot, 0)
            kv_in = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, gkv, 0, False),
                carry["kv"])
            dkv_in = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, gkv, 0, False),
                carry["dkv"])
            if remat:
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
            tok_in = jax.lax.dynamic_slice(
                tokens[:, :-1], (0, pos0), (spec.mbB, Sc))
            labels = jax.lax.dynamic_slice(
                tokens[:, 1:], (0, pos0), (spec.mbB, Sc))
            # per-chunk partial loss: sum(nll [* mask]) over the chunk's
            # slice, normalized by the *whole-sequence* token (or mask)
            # count so chunk losses and gradient seeds sum exactly to
            # the unchunked microbatch mean
            if "loss_mask" in batch:
                # loss_mask [m, mbB, S_tokens-1] is label-aligned, as in
                # the unchunked executor
                mask_full = get_mb(batch["loss_mask"], mb)
                mask = jax.lax.dynamic_slice(mask_full, (0, pos0),
                                             (spec.mbB, Sc))
                denom = jnp.maximum(jnp.sum(mask_full), 1.0)
            else:
                mask = None
                denom = float(spec.mbB * spec.S)   # whole-sequence mean

            def fwd_fn(bp, sp, pay, kvp):
                out, kv_out = _chunk_fwd_seq(spec, bp, flags_c, pay, kvp,
                                             pos0)
                return vary(out), vary(kv_out)

            def first_fn(bp, sp, kvp):
                pay = _embed_tokens(spec, sp, tok_in)
                # positions enter via pos0 inside the chunk fwd; the
                # embedding itself is position-free
                out, kv_out = _chunk_fwd_seq(spec, bp, flags_c, pay, kvp,
                                             pos0)
                return vary(out), vary(kv_out)

            def last_fn(bp, sp, pay, kvp):
                out, kv_out = _chunk_fwd_seq(spec, bp, flags_c, pay, kvp,
                                             pos0)
                ce = head_loss(spec, sp, out, labels, mask, denom=denom)
                return to_varying(ce), vary(kv_out)

            def wr(buf, val, slot):
                return jax.tree.map(
                    lambda b, p: jax.lax.dynamic_update_index_in_dim(
                        b, p, slot, 0), buf, val)

            def wr_act(carry, pay):
                return dict(carry, act=wr(carry["act"], pay, gslot))

            def wr_kv(carry, kv_out):
                return dict(carry, kv=wr(carry["kv"], kv_out, gkv))

            def wr_dkv(carry, dkv_out):
                return dict(carry, dkv=wr(carry["dkv"], dkv_out, gkv))

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

            # dKV cotangent: zeros for the first backward of the
            # microbatch (q == n_seq-1), the accumulated ring otherwise
            def dkv_cot():
                return jax.tree.map(
                    lambda a: jnp.where(q == ns - 1,
                                        jnp.zeros_like(a), a),
                    vary(dict(dkv_in)))

            def br_idle(carry):
                return carry, zero_pay

            def br_fwd_mid(carry):
                out, kv_out = fwd_fn(blocks_c, shared, vary(dict(x_in)),
                                     vary(dict(kv_in)))
                return wr_kv(wr_act(carry, x_in), kv_out), out

            def br_fwd_first(carry):
                out, kv_out = first_fn(blocks_c, shared,
                                       vary(dict(kv_in)))
                return wr_kv(carry, kv_out), out

            def br_fwd_last(carry):
                out, kv_out = fwd_fn(blocks_c, shared, vary(dict(x_in)),
                                     vary(dict(kv_in)))
                ce = head_loss(spec, shared, out, labels, mask,
                               denom=denom)
                carry = wr_kv(wr_act(carry, x_in), kv_out)
                return dict(carry, loss=carry["loss"] + ce,
                            nloss=carry["nloss"] + 1.0 / ns), zero_pay

            def br_bwd_mid(carry):
                dy = vary(dict(dy_in))
                _, vjp = jax.vjp(
                    lambda bp, pay, kvp: fwd_fn(bp, shared, pay, kvp),
                    vary(blocks_c), vary(dict(bnd_in)), vary(dict(kv_in)))
                gb_c, dx, dkv = vjp((dy, dkv_cot()))
                return wr_dkv(_add_block_grads(carry, gb_c), dkv), dx

            def br_bwd_first(carry):
                dy = vary(dict(dy_in))
                _, vjp = jax.vjp(
                    lambda bp, sp, kvp: first_fn(bp, sp, kvp),
                    vary(blocks_c), vary(shared), vary(dict(kv_in)))
                gb_c, gs, dkv = vjp((dy, dkv_cot()))
                carry = _add_block_grads(carry, gb_c)
                return wr_dkv(_add_shared_grads(carry, gs), dkv), zero_pay

            def br_bwd_last(carry):
                _, vjp = jax.vjp(
                    lambda bp, sp, pay, kvp: last_fn(bp, sp, pay, kvp),
                    vary(blocks_c), vary(shared), vary(dict(bnd_in)),
                    vary(dict(kv_in)))
                gb_c, gs, dx, dkv = vjp(
                    (to_varying(jnp.ones((), jnp.float32)), dkv_cot()))
                carry = _add_block_grads(carry, gb_c)
                return wr_dkv(_add_shared_grads(carry, gs), dkv), dx

            branches = [br_idle, br_fwd_mid, br_fwd_first, br_fwd_last,
                        br_bwd_mid, br_bwd_first, br_bwd_last]

            if remat:
                # R tick: hand the unit's boundary checkpoint from the
                # act ring to the remat ring (replay fuses into B's vjp)
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

                branches += [br_idle, br_idle, br_idle]   # W op slots
                branches += [br_rcp, br_rcp, br_rcp]

            carry, out = jax.lax.switch(op, branches, carry)

            # ---- route (identical to the unchunked executor, but the
            # payloads are 1/n_seq-size sequence-chunk boundaries) ----
            def sel(code):
                return jax.tree.map(
                    lambda a: jnp.where(snd == code, a,
                                        jnp.zeros_like(a)), out)
            perm_f = [(i, i + 1) for i in range(P_ - 1)]
            perm_b = [(i + 1, i) for i in range(P_ - 1)]
            perm_h = ([(P_ - 1, 0), (0, P_ - 1)] if P_ > 1 else [(0, 0)])
            moved_f = _ppermute(sel(SEND_FWD), pp, perm_f)
            moved_b = _ppermute(sel(SEND_BWD), pp, perm_b)
            hop_pay = jax.tree.map(lambda a, b: a + b,
                                   sel(SEND_HOPF), sel(SEND_HOPB))
            moved_h = _ppermute(hop_pay, pp, perm_h)

            arrive_f = jax.tree.map(
                lambda a, b: jnp.where(s_idx == 0, b, a), moved_f, moved_h)
            arrive_b = jax.tree.map(
                lambda a, b: jnp.where(s_idx == P_ - 1, b, a),
                moved_b, moved_h)

            def q_write(qu, slot, val):
                cur = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, jnp.maximum(slot, 0), 0, False), qu)
                val = jax.tree.map(
                    lambda new, old: jnp.where(slot >= 0, new, old),
                    val, cur)
                return jax.tree.map(
                    lambda a, vv: jax.lax.dynamic_update_index_in_dim(
                        a, vv, jnp.maximum(slot, 0), 0), qu, val)

            carry = dict(carry,
                         fq=pin_buf(q_write(carry["fq"], rcf, arrive_f)),
                         bq=pin_buf(q_write(carry["bq"], rcb, arrive_b)),
                         act=pin_buf(carry["act"]),
                         kv=pin_buf(carry["kv"]),
                         dkv=pin_buf(carry["dkv"]))
            if remat:
                carry = dict(carry, rmt=pin_buf(carry["rmt"]))
            return carry, None

        init = jax.tree.map(to_varying, carry_init())
        carry, _ = jax.lax.scan(tick, init, jnp.arange(tab.T))

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


def _make_seq_train_grads_phase(spec: PipelineSpec, mesh):
    """Phase-compiled seq executor — the
    :func:`repro.core.pipeline_runtime._make_train_grads_phase` twin
    with the KV-carry / dKV rings threaded through the pure-producer
    branch protocol: branches additionally return ``st_kv`` (the F
    tick's updated KV buffer) and ``st_dkv`` (the B tick's accumulated
    cotangent), written back outside the switch through trash-slotted
    ring updates."""
    from repro.core.pipeline_runtime import (_build_route,
                                             _exchange_ag_max,
                                             _pack_payload, _payload_words,
                                             _traced_once, _unpack_payload)
    from repro.core.tasktable import (B_OPS, BWD_FIRST, BWD_LAST, F_OPS,
                                      FWD_FIRST, FWD_LAST, FWD_MID, IDLE,
                                      R_OPS, RCP_MID, factor_phases,
                                      replay_phases)
    import numpy as np

    cfg = spec.cfg
    tab = spec.table
    P_, v, ns = tab.P, tab.v, tab.n_seq
    assert ns > 1 and not tab.has_w
    assert tab.placement_name == "interleaved", \
        "seq-chunked executor supports the interleaved placement only"
    pp = spec.pp_axis
    kernels_on = get_backend(spec.kernels).fuses
    Sc = spec.S // ns
    plan = factor_phases(tab)
    A = tab.arrays()
    stream = replay_phases(tab, plan)
    assert np.array_equal(stream, A), \
        "phase factorization is not a pure re-encoding of the table"
    remat = tab.has_r

    def offsets(depths):
        off = np.zeros(v, np.int64)
        total = 0
        for c in range(v):
            off[c] = total
            total += depths.get(c, 0)
        return jnp.asarray(off), total

    # one-tick-shifted row stream for the deferred (double-buffered)
    # route: tick t delivers tick t-1's payload with t-1's columns
    null_row = np.zeros((1, tab.P, 16), np.int32)
    null_row[..., 3:] = -1
    null_row[..., 5] = 0                            # SEND_NONE
    prev_stream = np.concatenate([null_row, stream[:-1]], axis=0)

    act_offsets, total_act = offsets(tab.act_depth)
    kv_offsets, total_kv = offsets(tab.kv_depth)
    r_offsets, total_rmt = offsets(tab.rmt_depth)
    flags_np = spec.layout.flags(cfg)
    M = spec.layout.M
    per = spec.layout.period
    G, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    Wb = _payload_words(spec, S=Sc)
    counts = {"embed": 0, "chunk": 0, "head": 0}
    codes = tuple(int(x) for x in np.unique(A[:, :, 0]))
    snds = frozenset(int(x) for x in np.unique(A[:, :, 5]))
    use_ag = P_ * spec.mbB * Wb * 2 <= _exchange_ag_max()

    def spmd(stage_iota, params, batch):
        s_idx = stage_iota[0]
        blocks = [jax.tree.map(lambda a: a[0], t) for t in params["blocks"]]
        flags = {k: jnp.asarray(vv)[s_idx] for k, vv in flags_np.items()}
        shared = {k: params[k] for k in params if k != "blocks"}
        dtype = jnp.dtype(cfg.compute_dtype)

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

        def chunk_core(blocks_c, pay, kvp, flags_c, pos0):
            counts["chunk"] += 1
            out, kv_out = _chunk_fwd_seq(spec, blocks_c, flags_c, pay,
                                         kvp, pos0)
            return vary(out), vary(kv_out)

        def embed_core(shared_p, tok):
            counts["embed"] += 1
            return vary(_embed_tokens(spec, shared_p, tok))

        def head_core(pay_out, shared_p, labels, mask, denom):
            counts["head"] += 1
            ce = head_loss(spec, shared_p, pay_out, labels, mask,
                           denom=denom)
            return to_varying(ce)

        jchunk = _traced_once(chunk_core)
        jembed = _traced_once(embed_core)
        jhead = _traced_once(head_core)

        zero_wire = to_varying(jnp.zeros((spec.mbB, Wb), jnp.uint16))
        zero_kv_val = vary({
            "k": jnp.zeros((M, per, spec.mbB, spec.S, G, hd), dtype),
            "v": jnp.zeros((M, per, spec.mbB, spec.S, G, hd), dtype)})
        zero_blocks_g = jax.tree.map(jnp.zeros_like, blocks)

        def zero_gs():
            return vary(jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), shared))

        def pin_buf(t):
            def one(a):
                if a.ndim == 7:
                    return shard(a, None, None, None, "dp", None, None,
                                 None)
                if a.ndim >= 3:
                    return shard(a, None, "dp", None)
                return a
            return jax.tree.map(one, t)

        def ring(slots):
            return pin_buf(jnp.zeros((slots + 1, spec.mbB, Wb),
                                     jnp.uint16))

        def kv_ring():
            return pin_buf(jax.tree.map(
                lambda a: jnp.zeros((total_kv + 1,) + a.shape, a.dtype),
                zero_kv_val))

        def carry_init():
            carry = {
                "fq": ring(tab.fq_depth),
                "bq": ring(tab.bq_depth),
                "act": ring(total_act),
                "kv": kv_ring(),
                "dkv": kv_ring(),
                "gb": zero_blocks_g,
                "gs": zero_gs(),
                "loss": jnp.zeros((), jnp.float32),
                "nloss": jnp.zeros((), jnp.float32),
            }
            if remat:
                carry["rmt"] = ring(total_rmt)
            return carry

        def rd(buf, i):
            return jax.lax.dynamic_index_in_dim(buf, i, 0, keepdims=False)

        def wr(buf, val, i):
            return jax.lax.dynamic_update_index_in_dim(buf, val, i, 0)

        def tick_core(carry, row_all):
            row = row_all[s_idx]
            op, c, mb, src = row[0], row[1], row[2], row[3]
            aslot, rslot = row[4], row[13]
            q, kvslot = row[14], row[15]
            pos0 = q * Sc
            gact = jnp.where(aslot < 0, total_act,
                             act_offsets[c] + jnp.maximum(aslot, 0))
            gkv = jnp.where(kvslot < 0, total_kv,
                            kv_offsets[c] + jnp.maximum(kvslot, 0))
            grm = jnp.where(rslot < 0, total_rmt,
                            r_offsets[c] + jnp.maximum(rslot, 0)) \
                if remat else None

            def blocks_at():
                blocks_c = [jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, c, 0, False),
                    t_) for t_ in blocks]
                flags_c = {k: jax.lax.dynamic_index_in_dim(vv, c, 0, False)
                           for k, vv in flags.items()}
                return blocks_c, flags_c

            def batch_inputs():
                tokens = rd(batch["tokens"], mb)
                tok_in = jax.lax.dynamic_slice(
                    tokens[:, :-1], (0, pos0), (spec.mbB, Sc))
                labels = jax.lax.dynamic_slice(
                    tokens[:, 1:], (0, pos0), (spec.mbB, Sc))
                if "loss_mask" in batch:
                    mask_full = rd(batch["loss_mask"], mb)
                    mask = jax.lax.dynamic_slice(mask_full, (0, pos0),
                                                 (spec.mbB, Sc))
                    denom = jnp.maximum(jnp.sum(mask_full), 1.0)
                else:
                    mask = None
                    denom = jnp.asarray(float(spec.mbB * spec.S))
                return tok_in, labels, mask, denom

            def bnd_read():
                a = rd(carry["act"], gact)
                if remat:
                    a = jnp.where(rslot >= 0, rd(carry["rmt"], grm), a)
                return a

            def kv_read(buf):
                return jax.tree.map(lambda a: rd(a, gkv), buf)

            def dkv_cot(dkv_in):
                # zeros seed the first backward of the microbatch
                return jax.tree.map(
                    lambda a: jnp.where(q == ns - 1, jnp.zeros_like(a),
                                        a), vary(dict(dkv_in)))

            z32 = to_varying(jnp.zeros((), jnp.float32))

            def zeros_gbd():
                return [vary(jax.tree.map(
                    lambda a: jnp.zeros(a.shape[1:], a.dtype), t))
                    for t in zero_blocks_g]

            def gs_of(gs_raw):
                return jax.tree.map(lambda z, g: g.astype(z.dtype),
                                    zero_gs(), gs_raw)

            def ret(out=None, gbd=None, gsd=None, ce=None, nl=None,
                    st_a=None, st_kv=None, st_dkv=None):
                return (out if out is not None else zero_wire,
                        gbd if gbd is not None else zeros_gbd(),
                        gsd if gsd is not None else zero_gs(),
                        ce if ce is not None else z32,
                        nl if nl is not None else z32,
                        st_a if st_a is not None else zero_wire,
                        st_kv if st_kv is not None else zero_kv_val,
                        st_dkv if st_dkv is not None else zero_kv_val)

            def br_idle(_):
                return ret()

            def br_fwd(_):
                is_first = op == FWD_FIRST
                is_last = op == FWD_LAST
                blocks_c, flags_c = blocks_at()
                tok_in, labels, mask, denom = batch_inputs()
                pin = rd(carry["fq"], jnp.maximum(src, 0))
                pay = jax.lax.cond(
                    is_first, lambda _: jembed(shared, tok_in),
                    lambda _: vary(_unpack_payload(spec, pin, S=Sc)),
                    None)
                out, kv_out = jchunk(blocks_c, pay,
                                     vary(kv_read(carry["kv"])), flags_c,
                                     pos0)
                ce = jax.lax.cond(
                    is_last,
                    lambda _: jhead(dict(out), shared, labels, mask,
                                    denom),
                    lambda _: z32, None)
                return ret(out=_pack_payload(spec, out, S=Sc), ce=ce,
                           nl=jnp.where(is_last, 1.0 / ns, 0.0),
                           st_a=pin, st_kv=kv_out)

            def br_bwd(_):
                is_first = op == BWD_FIRST
                is_last = op == BWD_LAST
                blocks_c, flags_c = blocks_at()
                tok_in, labels, mask, denom = batch_inputs()
                bnd = bnd_read()
                kv_in = kv_read(carry["kv"])
                pay_in = jax.lax.cond(
                    is_first, lambda _: jembed(shared, tok_in),
                    lambda _: vary(_unpack_payload(spec, bnd, S=Sc)),
                    None)
                (out, _), vjp = jax.vjp(
                    lambda bp, pay, kvp: jchunk(bp, pay, kvp, flags_c,
                                                pos0),
                    vary(blocks_c), vary(pay_in), vary(dict(kv_in)))
                qdy = _unpack_payload(
                    spec, rd(carry["bq"], jnp.maximum(src, 0)), S=Sc)

                def head_pull(_):
                    _, hvjp = jax.vjp(
                        lambda po, sp: jhead(po, sp, labels, mask,
                                             denom),
                        vary(dict(out)), vary(shared))
                    return hvjp(to_varying(jnp.ones((), jnp.float32)))

                dy, gs = jax.lax.cond(
                    is_last, head_pull,
                    lambda _: (vary(dict(qdy)), zero_gs()), None)
                gb_c, dx, dkv = vjp((dy, dkv_cot(kv_read(carry["dkv"]))))

                def embed_pull(_):
                    _, evjp = jax.vjp(
                        lambda sp: jembed(sp, tok_in), vary(shared))
                    (gs_e,) = evjp(vary(dict(dx)))
                    return gs_e

                gs = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), gs,
                    jax.lax.cond(is_first, embed_pull,
                                 lambda _: zero_gs(), None))
                return ret(out=_pack_payload(spec, dx, S=Sc), gbd=gb_c,
                           gsd=gs_of(gs), st_dkv=dkv)

            def br_rcp(_):
                return ret(st_a=rd(carry["act"], gact))

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
            out, gb_d, gs_d, ce, nl, st_a, st_kv, st_dkv = res

            is_f = (op >= FWD_MID) & (op <= FWD_LAST)
            is_b = sum((op == o) for o in B_OPS) > 0
            carry = dict(
                carry,
                act=wr(carry["act"], st_a,
                       jnp.where(is_f, gact, total_act)),
                kv=jax.tree.map(
                    lambda buf, val: wr(buf, val,
                                        jnp.where(is_f, gkv, total_kv)),
                    carry["kv"], st_kv),
                dkv=jax.tree.map(
                    lambda buf, val: wr(buf, val,
                                        jnp.where(is_b, gkv, total_kv)),
                    carry["dkv"], st_dkv))
            if remat:
                is_r = op >= RCP_MID
                carry = dict(carry, rmt=wr(
                    carry["rmt"], st_a, jnp.where(is_r, grm, total_rmt)))
            # only B ops produce gradient deltas (F/R/idle return exact
            # zeros): gate the accumulator traffic off every other tick
            # — see the core executor's tick_core for the rationale
            gb = jax.lax.cond(
                is_b,
                lambda t: [jax.tree.map(
                    lambda g, d: jax.lax.dynamic_update_index_in_dim(
                        g, jax.lax.dynamic_index_in_dim(g, c, 0, False)
                        + d, c, 0), gt, dt)
                    for gt, dt in zip(t, gb_d)],
                lambda t: list(t), carry["gb"])
            gs = jax.lax.cond(
                is_b,
                lambda t: jax.tree.map(lambda a, b: a + b, t, gs_d),
                lambda t: t, carry["gs"])
            carry = dict(carry, gb=gb, gs=gs,
                         loss=carry["loss"] + ce,
                         nloss=carry["nloss"] + nl)
            return carry, out, row

        def make_tick():
            route_x, route_l = _build_route(tab, P_, pp, snds, use_ag,
                                            s_idx)
            defer = tab.overlap and route_x.has_xdev
            xdev_have = [cd for cd in snds if cd not in LOCAL_SENDS]

            def skip_quiet(route_row_all, fq, bq, payload):
                # quiet ticks skip the collective rendezvous — the row
                # is replicated table data, so the predicate is
                # SPMD-uniform (see the core executor)
                if not xdev_have:
                    return fq, bq
                anyx = jnp.any(functools.reduce(
                    jnp.logical_or,
                    [route_row_all[:, 5] == cd for cd in xdev_have]))
                return jax.lax.cond(
                    anyx,
                    lambda a: route_x(a[0], a[1], a[2], route_row_all,
                                      route_row_all[s_idx]),
                    lambda a: (a[0], a[1]), (fq, bq, payload))

            def repin(carry):
                carry = dict(carry, act=pin_buf(carry["act"]),
                             kv=pin_buf(carry["kv"]),
                             dkv=pin_buf(carry["dkv"]))
                if remat:
                    carry = dict(carry, rmt=pin_buf(carry["rmt"]))
                return carry

            if not defer:
                def tick(carry, rows):
                    row_all, _ = rows
                    carry, out, row = tick_core(carry, row_all)
                    fq, bq = skip_quiet(row_all, carry["fq"],
                                        carry["bq"], out)
                    fq, bq = route_l(fq, bq, out, row)
                    return repin(dict(carry, fq=pin_buf(fq),
                                      bq=pin_buf(bq)))
                return tick, False

            # double-buffered exchange: the collective delivers LAST
            # tick's payload with last tick's routing row, independent
            # of this tick's compute (see the core executor); the
            # table's overlap mode gives cross-device consumers the
            # required 2-tick gap, local channels stay same-tick.
            def tick(carry, rows):
                row_all, prow_all = rows
                fq, bq = skip_quiet(prow_all, carry["fq"],
                                    carry["bq"], carry["wire"])
                carry, out, row = tick_core(carry, row_all)
                fq, bq = route_l(fq, bq, out, row)
                return repin(dict(carry, fq=pin_buf(fq),
                                  bq=pin_buf(bq), wire=out))

            return tick, True

        tick, defer = make_tick()
        carry0 = carry_init()
        if defer:
            carry0["wire"] = jnp.zeros((spec.mbB, Wb), jnp.uint16)
        carry, _ = jax.lax.scan(
            lambda cr, rw: (tick(cr, rw), None),
            jax.tree.map(to_varying, carry0),
            (jnp.asarray(stream), jnp.asarray(prev_stream)))

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

    call.trace_counts = counts
    call.phase_plan = plan
    return call


def _ppermute(x, axis, perm):
    """Tree-mapped ``lax.ppermute``; all-identity permutations (e.g. the
    P=1 hop wrap) skip the collective and pass the payload through."""
    if all(s == d for s, d in perm):
        return x
    return jax.tree.map(lambda a: jax.lax.ppermute(a, axis, perm), x)
