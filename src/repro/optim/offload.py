"""Chronos-Offload: host-side optimizer for the *deepest* chunks.

The paper's §5.1: deep-layer weights have the worst temporal locality
(updated first in backward, needed last in forward), so their optimizer
step — gradients down over PCIe, Adam on the host CPU (SIMD), quantized
bf16 weights back up — is hidden inside the warm-up/cool-down bubbles
that Chronos-Pipe structurally creates.

Two code paths:
- **host path** (this module, runs everywhere incl. the CPU container):
  master weights + momenta live as host numpy arrays; the update runs in
  a background thread (the "bubble"), overlapping the next step's shallow
  work; ``join()`` lands before the deep chunks' forward needs the new
  weights — mirroring Eq. (4)/(7)'s two bubble windows.
- **TPU memory-kind path**: on real TPU backends the same state is
  placed with ``memory_kind="pinned_host"`` shardings so XLA manages the
  PCIe transfers; selected automatically when the backend supports it.

The device keeps only bf16 weights (+ incoming grads transiently) for
offloaded chunks — the paper's ~1/3-of-model-state residency.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import OptimizerConfig
from repro.optim.schedules import lr_at


def backend_supports_pinned_host() -> bool:
    try:
        dev = jax.devices()[0]
        return "pinned_host" in {m.kind for m in dev.addressable_memories()}
    except Exception:
        return False


class HostAdamW:
    """Numpy AdamW over a pytree of host-resident fp32 states."""

    def __init__(self, params_subset, cfg: OptimizerConfig):
        self.cfg = cfg
        self.step = 0
        self.master = jax.tree.map(
            lambda a: np.array(a, np.float32, copy=True), params_subset)
        self.mu = jax.tree.map(np.zeros_like, self.master)
        self.nu = jax.tree.map(np.zeros_like, self.master)

    def update(self, grads_host, clip_coef: float = 1.0) -> Any:
        """grads_host: pytree of numpy fp32. Returns new bf16-able master
        tree (numpy fp32; caller casts on upload)."""
        cfg = self.cfg
        self.step += 1
        lr = float(lr_at(cfg, self.step))
        b1, b2, eps = cfg.beta1, cfg.beta2, cfg.eps
        bc1 = 1 - b1 ** self.step
        bc2 = 1 - b2 ** self.step

        def upd(g, mu, nu, w):
            g = np.array(g, np.float32, copy=True) * clip_coef
            mu *= b1
            mu += (1 - b1) * g
            nu *= b2
            nu += (1 - b2) * np.square(g)
            step_ = (mu / bc1) / (np.sqrt(nu / bc2) + eps)
            step_ += cfg.weight_decay * w
            w -= lr * step_
            return w

        self.master = jax.tree.map(upd, grads_host, self.mu, self.nu,
                                   self.master)
        return self.master


class ChronosOffloadRunner:
    """Asynchronous deep-chunk optimizer: offload -> host update -> upload,
    overlapped with the pipeline's warm-up/cool-down bubbles.

    Usage per step:
        runner.submit(deep_grads_device)     # after backward (cooldown)
        ... launch next step's shallow work ...
        new_deep = runner.collect()          # before deep fwd (warm-up)
    """

    def __init__(self, deep_params, cfg: OptimizerConfig,
                 target_dtype=jnp.bfloat16):
        self.opt = HostAdamW(deep_params, cfg)
        self.dtype = target_dtype
        self._thread: Optional[threading.Thread] = None
        self._result: Optional[Any] = None
        self.stats: Dict[str, float] = {"submits": 0, "overlapped": 0}

    def submit(self, deep_grads, clip_coef: float = 1.0) -> None:
        assert self._thread is None, "previous offload not collected"
        grads_host = jax.tree.map(
            lambda a: np.array(a, np.float32, copy=True),
            deep_grads)                                       # PCIe down
        self._error: Optional[BaseException] = None

        def work():
            try:
                with jax.profiler.TraceAnnotation("host_update"):
                    self._result = self.opt.update(grads_host, clip_coef)
            except BaseException as e:                        # noqa: BLE001
                self._error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()
        self.stats["submits"] += 1

    def collect(self) -> Any:
        assert self._thread is not None
        busy_before = self._thread.is_alive()
        self._thread.join()
        if not busy_before:
            self.stats["overlapped"] += 1
        self._thread = None
        if self._error is not None:
            raise self._error
        res = jax.tree.map(
            lambda a: jnp.asarray(a, self.dtype), self._result)  # PCIe up
        self._result = None
        return res


def split_deep_shallow(blocks_grads_or_params, v: int,
                       num_offload_chunks: int):
    """Split stacked block trees (leaves [P, v, M, ...]) along the chunk
    axis into (shallow, deep).  Deep = last ``num_offload_chunks``."""
    cut = v - num_offload_chunks

    def deep(a):
        return a[:, cut:]

    def shallow(a):
        return a[:, :cut]

    return (jax.tree.map(shallow, blocks_grads_or_params),
            jax.tree.map(deep, blocks_grads_or_params))


def merge_deep_shallow(shallow_tree, deep_tree):
    return jax.tree.map(
        lambda s, d: jnp.concatenate([s, d], axis=1), shallow_tree,
        deep_tree)
