"""One run of the four-chip cell's path at a small size on four virtual
CPU devices, printed as the result line; with ``noexchange`` the
boundary exchange between stages is left out (every ppermute a no-op).

    python -m bench.tests.tiny4 [noexchange]
"""
import json
import os
import sys
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"


def main(fault: str) -> None:
    import jax

    from bench import check, harness
    from bench.tests.test_faults import ATTN, E2E
    if fault == "noexchange":
        import repro.core.pipeline_runtime as PR
        os.environ["REPRO_EXCHANGE_AG_MAX"] = "0"
        PR._ppermute = lambda x, axis, perm: x
    cell = "deepseek-7b-4stages.chronos-p4"
    res = harness.run_cell(
        {"name": cell}, dict(ATTN, num_hidden_layers=8),
        dict(harness.load_traffic("chronos-p4"), seq_len=64),
        seed=2 ** 31 + 3, seconds=0.2, trace=False,
        devices=jax.devices()[:4], limits=check.load_limits(cell), e2e=E2E,
        per_layer=[], t_start=time.monotonic())
    print(json.dumps(res))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "")
