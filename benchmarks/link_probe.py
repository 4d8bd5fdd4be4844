"""Host<->device link ceilings of the machine a chip run lands on: plain
D2H, H2D, device -> ``pinned_host`` and ``pinned_host`` -> numpy, each on
fresh arrays (``np.asarray`` caches its host copy, so a repeated read of
one array measures nothing).  The ceilings PERF.md section 2 quotes come
from here.

Run through the chip tool:  python benchmarks/link_probe.py [--mb 512]
Exits nonzero, printing no result, when JAX finds no accelerator.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--mb", type=int, default=512, help="MiB per array")
    parser.add_argument("--rounds", type=int, default=3)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchsnapshot_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print(
            f"link_probe: no accelerator (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r})",
            file=sys.stderr,
        )
        return 2
    n = args.mb * (1 << 20) // 4
    gib = args.mb / 1024
    fresh = jax.jit(lambda i: jnp.full((n,), i, jnp.float32))
    pinned = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        return out, gib / (time.perf_counter() - t0)

    legs: dict = {"d2h": [], "h2d": [], "d2pinned": [], "pinned2numpy": []}
    for i in range(args.rounds):
        a = jax.block_until_ready(fresh(np.float32(i)))
        host, rate = timed(lambda: np.asarray(a))
        legs["d2h"].append(rate)
        _, rate = timed(lambda: jax.device_put(host, dev))
        legs["h2d"].append(rate)
        b = jax.block_until_ready(fresh(np.float32(i + 0.5)))
        p, rate = timed(lambda: jax.device_put(b, pinned))
        legs["d2pinned"].append(rate)
        _, rate = timed(lambda: np.asarray(p))
        legs["pinned2numpy"].append(rate)
    print(
        json.dumps(
            {
                "probe": "link",
                "platform": dev.platform,
                "device_kind": dev.device_kind,
                "mib_per_array": args.mb,
                "unit": "GiB/s, one value per round, first round cold",
                **{k: [round(r, 3) for r in v] for k, v in legs.items()},
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
