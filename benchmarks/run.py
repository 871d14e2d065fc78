"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Table mapping:

  tab1_*  relative emulation cost            (paper Tab. 1)
  tab2_*  proxy-activation necessity         (paper Tab. 2)
  tab5_*  accuracy: model/inject/fine-tune   (paper Tab. 4+5)
  tab6_*  gradient checkpointing             (paper Tab. 6)
  tab7_*  per-iteration runtime              (paper Tab. 7, headline)
  fig2_*  error profile smoothness           (paper Fig. 2)
  serve_* continuous-batching engine vs static baseline
  search_* hardware-aware approximation search vs uniform backends
  dispatch_* one-compile heterogeneous dispatch: O(1) compile scaling
  variation_* chip fleets: variation-aware training, drift + recalibration
  train_speed_* approximate-backward training: gated int8 gradients +
              quantized optimizer state vs the exact baseline
  fabric_*  N-replica serving fabric: scaling, health-aware routing,
              recal-under-churn, solo-engine oracle bit-match

Every benchmark also writes a JSON artifact under results/ through
``benchmarks.common.write_json``.  ``benchmarks.roofline`` (fused vs
composed emulated decode, dry-run derived) runs as a subprocess because
it must set the host-device-count XLA flag before jax initializes; the
child is held to the CPU, since the parent may hold the chip.
"""
from __future__ import annotations

import os
import subprocess
import sys
import traceback


def _roofline(fast: bool) -> None:
    cmd = [sys.executable, os.path.join(os.path.dirname(__file__), "roofline.py")]
    if fast:
        cmd.append("--smoke")
    # a dry-run on fake host devices: on a TPU host this parent already
    # holds the chip, which a child reaching for it would hang on
    subprocess.run(cmd, check=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})


def main() -> None:
    fast = "--full" not in sys.argv
    from benchmarks import (
        bench_accuracy,
        bench_checkpoint,
        bench_dispatch,
        bench_error_profile,
        bench_fabric,
        bench_kernels,
        bench_proxy,
        bench_runtime,
        bench_search,
        bench_serve,
        bench_train_speed,
        bench_variation,
    )

    print("name,us_per_call,derived")
    jobs = [
        ("tab1", lambda: bench_kernels.run()),
        ("tab7", lambda: bench_runtime.run()),
        ("fig2", lambda: bench_error_profile.run()),
        ("tab6", lambda: bench_checkpoint.run()),
        ("tab2", lambda: bench_proxy.run(steps=30 if fast else 100)),
        ("tab5", lambda: bench_accuracy.run(steps=30 if fast else 100)),
        ("serve", lambda: bench_serve.run(smoke=fast)),
        ("search", lambda: bench_search.run(smoke=fast)),
        ("dispatch", lambda: bench_dispatch.run(smoke=fast)),
        ("variation", lambda: bench_variation.run(smoke=fast)),
        ("train_speed", lambda: bench_train_speed.run(smoke=fast)),
        ("fabric", lambda: bench_fabric.run(smoke=fast)),
        ("roofline", lambda: _roofline(fast)),
    ]
    from benchmarks import common

    failures = 0
    for name, job in jobs:
        try:
            job()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name}_FAILED,0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
            # a job that died after emit() leaves partial rows buffered;
            # they must not leak into the next job's JSON artifact
            common.discard_rows()

    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
