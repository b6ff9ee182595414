"""Warm the JAX compilation cache for the exact shapes bench.py runs.

Each shape compiles for about a minute on a TPU, and a cold shape hit
mid-measurement stalls the throughput phase for the whole compile.
Warming in ONE dedicated process — with progress timestamps — lets a
later bench run on the same machine start warm from the persistent
cache (utils/compile_cache.py: ``JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``), and a timeout here loses at most the shape
in flight. On the chip tool the cache does not outlive a call: warm and
measure in one command.

Mirrors bench.py's verifier construction exactly: the shared-cache
default (miss-ladder shapes via warmup(full=True)) AND the
BENCH_SHARE_CACHE=0 config (fused shapes), for each requested validator
count.

Usage: python tools/warm_kernels.py [n_validators ...]   (default: 4)
"""

import hashlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    val_counts = [int(a) for a in sys.argv[1:]] or [4]
    t0 = time.time()
    import jax

    print(f"[{time.time()-t0:7.1f}s] backend={jax.default_backend()} "
          f"devices={jax.devices()}", flush=True)

    from txflow_tpu.types.priv_validator import MockPV
    from txflow_tpu.types.validator import Validator, ValidatorSet
    from txflow_tpu.utils.compile_cache import use_compile_cache
    from txflow_tpu.verifier import DeviceVoteVerifier, VerifyCache

    print(f"[{time.time()-t0:7.1f}s] compile cache {use_compile_cache()}",
          flush=True)

    bucket = int(os.environ.get("BENCH_BUCKET", "4096"))
    for n_vals in val_counts:
        # same deterministic valset construction as bench.py (only the
        # [V,...] table shape matters for compilation)
        pvs = [
            MockPV(hashlib.sha256(b"localnet-val%d" % i).digest())
            for i in range(n_vals)
        ]
        vs = ValidatorSet(
            [Validator.from_pub_key(pv.get_pub_key(), 10) for pv in pvs]
        )
        for label, cache in (("cached/miss-ladder", VerifyCache()), ("no-cache/fused", None)):
            ver = DeviceVoteVerifier(
                vs, buckets=(bucket, 4 * bucket), shared_cache=cache
            )
            t = time.time()
            ver.warmup(full=True)
            print(f"[{time.time()-t0:7.1f}s] n_vals={n_vals} {label} "
                  f"warm in {time.time()-t:.1f}s", flush=True)


if __name__ == "__main__":
    main()
