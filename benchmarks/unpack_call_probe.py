"""What ONE member call of a slab's unpack costs a consume worker, by how
the call's runtime offset reaches the device (PERF.md §5, PR 37).

``--threads`` threads share one chip, as a restore's consume workers do.
Each puts ``--slabs`` host slabs of ``--slab-mb`` and makes ``--members``
member calls of ``--shape`` float32 on each, exactly as
``ops.device_pack.unpack_slab_to_device`` does (``jax.device_put`` of the
slab as uint32 words, then a slice-and-bitcast program a member), timing
every call on the host clock.  A pass is one way of handing the offsets
over, and of cutting a slab's members into calls:

- ``host``: ``fn(slab, np.int32(off))``: a numpy scalar a call, which jit
  transfers host→device inside the call (the package before PR 37);
- ``dev``: the slab's offsets put as ONE ``int32`` vector right after the
  slab, split into device-resident ``int32[]`` scalars by one small program,
  and every member call made with those (no host argument);
- ``group<k>`` (k = 2, 4, 8): as ``dev``, but members go k at a time through
  one program a (signature, k) that returns k arrays, the remainder one by
  one; its compile seconds are in the ``compile`` line;
- ``mix``: as ``dev``, the members cut greedily into calls of 8, 4, 2 and 1
  (20 members: 8 + 8 + 4, three calls);
- ``all``: as ``dev``, every member in ONE call, which is what the package
  does with a slab's members of one signature;
- ``dev_direct``, ``mix_direct``: as ``dev`` and ``mix``, but the offset
  vector is handed to the split program as a numpy array (ONE implicit
  transfer inside that call) instead of ``jax.device_put`` first: what the
  explicit put costs.

Each pass runs twice: ``inflight`` (calls right after the put: the first
call holds the wait for the slab, as in a restore) and ``landed`` (the
thread waits for its slab before the first call: what a call costs by
itself, the other threads' transfers still on the link).  Passes run in
the order A B C … C B A, so drift shows as a difference between the two
halves.  A line a (pass, phase): ``first_call_ms`` (mean of the first
member call of a slab), ``rest_call_ms`` (mean and median of every other
call), ``args_ms`` (the vector's put and its split), ``slab_ms`` (the
worker inside one slab's calls, ``unpack/dispatch`` as the package spans
it) and ``gb_s`` (all slabs' bytes over the wall of the pass, every output
waited for).

Run through the chip tool (PERF.md §6's table, PR 37, came from this line,
twice: ``probe1.jsonl`` before ``mix`` and the ``*_direct`` passes were
added, ``probe2.jsonl`` with them; ``all`` was added after both):
    chiprun --chips 1 -- python benchmarks/unpack_call_probe.py
Exits nonzero, printing no result, when JAX finds no accelerator
(``--cpu`` runs it tiny on the CPU: control flow only, never a time).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

GROUPS = (2, 4, 8)


def _member_program(shape):
    """As many members of one float32 signature as it is handed offsets, out
    of one slab of uint32 words: what ``ops.device_pack._jitted_unpack``'s
    program does (the probe keeps its own copy, so it reads the same on any
    commit).  jit keeps an executable a number of offsets."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    n = int(np.prod(shape))

    def unpack_k(slab, *offs):
        return tuple(
            lax.bitcast_convert_type(
                lax.dynamic_slice(slab, (off,), (n,)), jnp.float32
            ).reshape(shape)
            for off in offs
        )

    return jax.jit(unpack_k)


def _split_program(n):
    import jax

    return jax.jit(lambda vec: tuple(vec[i] for i in range(n)))


class _Pass:
    """One way of making a slab's member calls; ``calls(slab, device)``
    returns (outputs, per-call ns, ns spent on the arguments).  ``ks``:
    the sizes of call a slab's members are cut into, largest first, each
    used as often as it fits (``(1,)``: a call a member)."""

    def __init__(self, name, word_offs, shape, ks=(1,), direct=False):
        import numpy as np

        self.name = name
        self.direct = direct
        self.word_offs = np.asarray(word_offs, np.int32)
        self.ks = ks
        self.fn = _member_program(tuple(shape))
        self.split = _split_program(len(word_offs))

    def calls(self, slab, device):
        import jax
        import numpy as np

        ns = []
        outs = []
        if self.name == "host":
            for off in self.word_offs:
                t = time.perf_counter_ns()
                outs.extend(self.fn(slab, np.int32(off)))
                ns.append(time.perf_counter_ns() - t)
            return outs, ns, 0
        t0 = time.perf_counter_ns()
        if self.direct:
            offs = self.split(self.word_offs)
        else:
            offs = self.split(jax.device_put(self.word_offs, device))
        args_ns = time.perf_counter_ns() - t0
        i = 0
        for k in self.ks:
            while i + k <= len(offs):
                t = time.perf_counter_ns()
                outs.extend(self.fn(slab, *offs[i : i + k]))
                ns.append(time.perf_counter_ns() - t)
                i += k
        return outs, ns, args_ns


def _run_pass(p, phase, hosts, device, n_slabs):
    """Every thread puts ``n_slabs`` slabs and makes their calls; returns
    the line of the pass."""
    import jax

    lock = threading.Lock()
    firsts, rests, args, slabs = [], [], [], []
    errors = []

    def worker(k):
        try:
            prev = None
            for j in range(n_slabs):
                slab = jax.device_put(hosts[k][j % len(hosts[k])], device)
                if phase == "landed":
                    slab.block_until_ready()
                t0 = time.perf_counter_ns()
                outs, ns, args_ns = p.calls(slab, device)
                span = time.perf_counter_ns() - t0
                del slab
                with lock:
                    firsts.append(ns[0])
                    rests.extend(ns[1:])
                    args.append(args_ns)
                    slabs.append(span)
                # a worker of the pipeline is one slab ahead of the device
                # at most: the next slab's first call waits for its transfer
                if prev is not None:
                    jax.block_until_ready(prev)
                    for a in prev:
                        a.delete()
                prev = outs
            jax.block_until_ready(prev)
            for a in prev:
                a.delete()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(k,)) for k in range(len(hosts))
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    moved = n_slabs * sum(h[0].nbytes for h in hosts)
    ms = 1e-6
    rests = rests or [0]  # a slab in one call has no other
    return {
        "pass": p.name, "phase": phase, "threads": len(hosts),
        "slabs": len(firsts), "calls_a_slab": 1 + len(rests) // len(firsts),
        "first_call_ms": statistics.fmean(firsts) * ms,
        "rest_call_ms": statistics.fmean(rests) * ms,
        "rest_call_median_ms": statistics.median(rests) * ms,
        "rest_call_p90_ms": (statistics.quantiles(rests, n=10)[-1] if len(rests) > 1 else rests[0]) * ms,
        "args_ms": statistics.fmean(args) * ms,
        "slab_ms": statistics.fmean(slabs) * ms,
        "wall_s": wall, "gb_s": moved / wall / 1e9,
    }


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--threads", type=int, nargs="+", default=[4, 1])
    parser.add_argument("--slabs", type=int, default=12, help="a thread a pass")
    parser.add_argument("--slab-mb", type=float, default=146.0)
    parser.add_argument("--members", type=int, default=20)
    parser.add_argument("--shape", type=int, nargs="+", default=[768, 2048])
    parser.add_argument("--out", default="chiprun_out/pr37/probe.jsonl")
    parser.add_argument("--cpu", action="store_true")
    args = parser.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.slab_mb, args.shape, args.slabs = 0.0, [8, 16], 3

    import jax
    import numpy as np

    device = jax.devices()[0]
    if device.platform == "cpu" and not args.cpu:
        print("no accelerator: nothing measured", file=sys.stderr)
        return 1
    member_words = int(np.prod(args.shape))
    slab_words = max(int(args.slab_mb * 1e6) // 4, args.members * member_words)
    word_offs = [i * member_words for i in range(args.members)]
    rng = np.random.default_rng(0)
    # two faulted host slabs a thread, put in turn
    hosts = [
        [
            rng.integers(0, 2**32, size=slab_words, dtype=np.uint32)
            for _ in range(2)
        ]
        for _ in range(max(args.threads))
    ]
    passes = [_Pass("host", word_offs, args.shape), _Pass("dev", word_offs, args.shape)]
    passes += [_Pass(f"group{k}", word_offs, args.shape, ks=(k, 1)) for k in GROUPS]
    passes += [
        _Pass("mix", word_offs, args.shape, ks=(8, 4, 2, 1)),
        _Pass("all", word_offs, args.shape, ks=(args.members,)),
        _Pass("dev_direct", word_offs, args.shape, direct=True),
        _Pass("mix_direct", word_offs, args.shape, ks=(8, 4, 2, 1), direct=True),
    ]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:

        def emit(line):
            line["device"] = device.device_kind
            f.write(json.dumps(line) + "\n")
            f.flush()
            print(json.dumps(line), flush=True)

        # compile every program (and both argument forms of each) outside
        # the passes; a group program's seconds are the issue's bound
        warm = jax.device_put(hosts[0][0], device)
        for p in passes:
            t0 = time.perf_counter()
            jax.block_until_ready(p.calls(warm, device)[0])
            emit({"compile": p.name, "seconds": time.perf_counter() - t0})
        del warm
        for n_threads in args.threads:
            for phase in ("inflight", "landed"):
                for p in passes + passes[::-1]:
                    emit(_run_pass(p, phase, hosts[:n_threads], device, args.slabs))
    return 0


if __name__ == "__main__":
    sys.exit(main())
