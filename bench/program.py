"""The system under test, as the benchmark drives it.

Builds the training step through the program's public entry point
(``repro.launch.steps.make_pipeline_train_step``), places the
benchmark's seeded weights in the program's stage-stacked layout in one
jitted call, and reads back what the check compares: the loss of each
step, the first gradient as AdamW received it (``mu / (1 - beta1)``
after one step) and the change of the fp32 master weights.  Everything
here is keyed by the benchmark's own (layer, leaf) names.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import model as M
from bench import weights as W


def program_config(m: M.Model):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=m.name, family="dense", num_layers=m.layers,
                       d_model=m.d, num_heads=m.heads,
                       num_kv_heads=m.kv_heads, d_ff=m.ff, head_dim=m.hd,
                       rope_theta=m.rope_theta, vocab_size=m.vocab_rows,
                       tie_embeddings=m.tie, norm_eps=m.eps, act="silu",
                       param_dtype=m.param_dtype,
                       compute_dtype=m.param_dtype)


def optimizer_config(traffic: dict):
    from repro.configs.base import OptimizerConfig
    return OptimizerConfig(**traffic["optimizer"])


def _path(p) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)


def _same(path, shape, dtype, want_shape, want_dtype):
    """The program's leaf is the one the benchmark describes."""
    if tuple(shape) != tuple(want_shape) or dtype != jnp.dtype(want_dtype):
        raise ValueError(f"{path}: the program holds {tuple(shape)} "
                         f"{dtype}, the benchmark describes "
                         f"{tuple(want_shape)} {want_dtype}")


@dataclasses.dataclass
class Program:
    """One compiled training step with its shardings and layout."""
    model: M.Model
    traffic: dict
    devices: list
    step: object = None          # (params, opt, batch) -> ...
    compiled: object = None      # the compiled step, with its HLO
    in_sh: tuple = None
    structs: tuple = None
    spec: object = None
    memory: object = None        # compiled.memory_analysis()

    def __post_init__(self):
        from repro.configs.base import (ParallelPlan, RecomputeConfig,
                                        ShapeConfig)
        from repro.launch.steps import make_pipeline_train_step
        from repro.models.sharding import make_mesh
        t, pl = self.traffic, self.traffic["plan"]
        mesh = make_mesh((pl["P"],), ("pp",), devices=self.devices[:pl["P"]])
        rules = {"pp": "pp", "dp": None, "tp": None, "fsdp": None}
        plan = ParallelPlan(
            dp_axes=(), tp_axis=None, pp_axis="pp", schedule=pl["schedule"],
            num_chunks=pl["num_chunks"], num_microbatches=t["microbatches"],
            microbatch_size=t["microbatch_size"],
            recompute=RecomputeConfig(**pl["recompute"]),
            kernels=pl["kernels"], wire=pl["wire"],
            zero_stage=pl["zero_stage"])
        shape = ShapeConfig(self.model.name, t["seq_len"] + 1,
                            t["microbatches"] * t["microbatch_size"], "train")
        extras = {}
        fn, self.structs, self.in_sh, out_sh = make_pipeline_train_step(
            program_config(self.model), shape, plan,
            optimizer_config(t), mesh, rules, extras=extras)
        self.spec = extras["spec"]
        if self.spec.layout.L_pad != self.model.layers:
            raise ValueError("the cell's depth must fill every (stage, "
                             "chunk) block")
        self.jitted = jax.jit(fn, in_shardings=self.in_sh,
                              out_shardings=out_sh, donate_argnums=(0, 1))
        self.step = self.compiled = self.jitted.lower(
            *self.structs).compile()
        self.memory = self.step.memory_analysis()
        self._placer = self._make_placer()

    # -- layout ---------------------------------------------------------
    def block_layers(self) -> np.ndarray:
        """Global layer index of each stacked block position [P, v, M]
        (one structural period: every layer of a cell is alike)."""
        lay = self.spec.layout
        if lay.period != 1:
            raise ValueError("a cell's layers must all be of one kind")
        out = np.zeros((lay.P, lay.v, lay.M), np.int64)
        for d in range(lay.P):
            for c in range(lay.v):
                for j in range(lay.M):
                    out[d, c, j] = lay.global_idx(d, c, j)
        return out

    def _make_placer(self):
        m = self.model
        leaves = {p: (i, s, init, dt) for i, (p, s, init, dt) in
                  enumerate(M.layer_leaves(m))}
        shared = {p: (i, s, init, dt) for i, (p, s, init, dt) in
                  enumerate(M.shared_leaves(m))}
        gidx = self.block_layers()
        params_s = self.structs[0]

        def make(seed):
            key0 = W.base_key(seed)

            def block(path, struct):
                i, s, init, dt = leaves[path]
                _same(path, struct.shape[3:], struct.dtype, s, dt)
                arr = jnp.stack([W.leaf(key0, int(g), i, s, init, dt)
                                 for g in gidx.ravel()])
                return arr.reshape(struct.shape)

            def one_shared(path, struct):
                i, s, init, dt = shared[path]
                _same(path, struct.shape, struct.dtype, s, dt)
                return W.leaf(key0, W.SHARED, i, s, init, dt)

            out = {"blocks": [jax.tree_util.tree_map_with_path(
                lambda p, a: block(_path(p), a), b)
                for b in params_s["blocks"]]}
            for k in params_s:
                if k != "blocks":
                    out[k] = jax.tree_util.tree_map_with_path(
                        lambda p, a, k=k: one_shared(f"{k}.{_path(p)}", a),
                        params_s[k])
            return out

        return make

    # -- state ----------------------------------------------------------
    def init_state(self, seed: int):
        """Parameters from the seed, in the program's layout, and the
        program's AdamW state for them: each one jitted call."""
        from repro.optim import adamw_init
        params = jax.jit(self._placer, out_shardings=self.in_sh[0])(
            np.uint32(W.seed32(seed)))
        opt = jax.jit(adamw_init, out_shardings=self.in_sh[1])(params)
        return params, opt

    def put_batch(self, rows: np.ndarray):
        t = self.traffic
        tok = rows.reshape(t["microbatches"], t["microbatch_size"], -1)
        return {"tokens": jax.device_put(tok, self.in_sh[2]["tokens"])}

    # -- readings -------------------------------------------------------
    def _by_layer(self, tree) -> dict:
        """{(layer, path): float} from a tree of per-position norms."""
        gidx = self.block_layers()
        out = {}
        for b in tree["blocks"]:
            for p, a in jax.tree_util.tree_flatten_with_path(b)[0]:
                a = np.asarray(a)
                for pos, g in np.ndenumerate(gidx):
                    out[(int(g), _path(p))] = float(a[pos])
        for k in tree:
            if k != "blocks":
                for p, a in jax.tree_util.tree_flatten_with_path(tree[k])[0]:
                    out[(-1, f"{k}.{_path(p)}")] = float(np.asarray(a))
        return out

    @staticmethod
    def _norms(tree):
        def blk(a):
            return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                    axis=tuple(range(3, a.ndim))))

        def one(a):
            return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
        out = {"blocks": [jax.tree.map(blk, b) for b in tree["blocks"]]}
        out.update({k: jax.tree.map(one, tree[k]) for k in tree
                    if k != "blocks"})
        return out

    def first_grad_norms(self, opt) -> dict:
        """After one step: ||mu|| / (1 - beta1) is the norm of the
        clipped gradient AdamW received, per (layer, leaf)."""
        b1 = self.traffic["optimizer"]["beta1"]
        norms = jax.jit(lambda mu: self._norms(
            jax.tree.map(lambda a: a / (1.0 - b1), mu)))(opt["mu"])
        return self._by_layer(norms)

    def first_grad_sketch(self, opt, seed: int) -> dict:
        """After one step: the ``weights.sketch`` of the clipped gradient
        AdamW received (``mu / (1 - beta1)``), per (layer, leaf)."""
        b1 = self.traffic["optimizer"]["beta1"]
        m = self.model
        index = {p: i for i, (p, *_) in enumerate(M.layer_leaves(m))}
        sindex = {p: i for i, (p, *_) in enumerate(M.shared_leaves(m))}
        gidx = self.block_layers()

        def fn(mu, s):
            key0 = W.base_key(s)
            out = {}
            for b in mu["blocks"]:
                for p, a in jax.tree_util.tree_flatten_with_path(b)[0]:
                    path = _path(p)
                    for pos, g in np.ndenumerate(gidx):
                        out[f"{int(g)}|{path}"] = W.sketch(
                            key0, int(g), index[path],
                            a[pos].astype(jnp.float32) / (1.0 - b1))
            for k in mu:
                if k != "blocks":
                    for p, a in jax.tree_util.tree_flatten_with_path(
                            mu[k])[0]:
                        path = f"{k}.{_path(p)}"
                        out[f"-1|{path}"] = W.sketch(
                            key0, W.SHARED, sindex[path],
                            a.astype(jnp.float32) / (1.0 - b1))
            return out

        out = jax.jit(fn)(opt["mu"], np.uint32(W.seed32(seed)))
        return {(int(k.split("|")[0]), k.split("|")[1]): np.asarray(v)
                for k, v in out.items()}

    def change_norms(self, opt, seed: int) -> dict:
        """||master - initial||, the initial weights drawn again from the
        seed inside the same jitted call."""
        def fn(master, s):
            init = self._placer(s)
            return self._norms(jax.tree.map(
                lambda a, b: a - b.astype(jnp.float32), master, init))
        norms = jax.jit(fn)(opt["master"], np.uint32(W.seed32(seed)))
        return self._by_layer(norms)
