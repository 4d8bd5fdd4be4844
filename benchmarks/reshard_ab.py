"""The resharding restore's mechanisms, one against the other, in ONE
process on ONE snapshot: the four-chip cell's state
(``ouro-2.6b-4chip.elastic_resume``: saved under dp2 x tp2 on a RAM sink,
restored into fresh dp1 x tp4 templates), restored in turn as

- ``parent``:   host assembly buffers, no populate (the code before PR 31);
- ``populate``: host assembly buffers, each mapped piece's pages asked of
                the kernel in one call before the copy;
- ``twice``:    the populate, then the piece put as it lies on EVERY
                device that holds a box of it and cut on each, no host
                buffer (the code of PR 31 to 33: a column piece crosses
                the host link once a device that shares it);
- ``direct``:   the populate, then the piece put ONCE, on one of those
                devices, every box cut there and the siblings' boxes
                moved device to device (the package as it is).

Each restore is timed as the cell times it (fresh template, restore, wait
for every leaf), with a 20 ms poll of the fullest device's ``bytes_in_use``
and the program's counters beside it; the first restore of a variant is
held against the reference digests and left out of the summary.  The
set-up takes no train step (the cell's two steps cost 28 s of compile and
move no byte of the restore).

Run through the chip tool:
    chiprun --chips 4 -- python benchmarks/reshard_ab.py
``--tiny`` is the same file at tiny widths on the CPU's virtual devices.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "ouro-2.6b-4chip.elastic_resume"
TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=128, vocab_size=256, num_hidden_layers=2,
    max_position_embeddings=64,
)


def _put_twice_and_cut(self, src, read_box, overlaps):
    """``_DirectLeaf._put_and_cut`` as PR 31 to 33 had it: a box's bytes
    (the piece whole, for a column box) go over the host link to each
    device that holds the box, and are cut there."""
    import jax

    from torchsnapshot_tpu import obs
    from torchsnapshot_tpu.ops.device_pack import cut_box_on_device
    from torchsnapshot_tpu.preparers.overlap import is_dim0_slab, relative_slices

    sends = []
    for inter, lbox in overlaps:
        devs = self.local_boxes[lbox]
        if is_dim0_slab(inter, read_box):
            rows = relative_slices(inter, read_box)[:1]
            sends.append((src[rows] if rows else src, None, None, devs))
        else:
            start = tuple(i - r for i, r in zip(inter[0], read_box[0]))
            sends.append((src, start, inter[1], devs))
    link_bytes = sum(view.nbytes * len(devs) for view, _, _, devs in sends)
    with obs.span(
        "reshard/direct", bytes=link_bytes, handoff_bytes=0,
        devices=sum(len(devs) for _, _, _, devs in sends),
        cut=any(start is not None for _, start, _, _ in sends),
    ):
        placed, wide = {}, []
        for view, start, sizes, devs in sends:
            for dev in devs:
                with obs.span("h2d/put", bytes=view.nbytes, device=dev.id):
                    arr = jax.device_put(view, dev)
                if start is not None:
                    wide.append(arr)
                    arr = cut_box_on_device(arr, start, sizes)
                placed[dev] = arr
        obs.counter(obs.RESHARD_LINK_BYTES).inc(link_bytes)
        jax.block_until_ready(list(placed.values()))
        for arr in wide:
            arr.delete()
    return placed


@contextlib.contextmanager
def _variant(name: str):
    """The package patched, for the length of one restore, into the
    mechanism ``name`` names: no knob of the package selects them."""
    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.preparers import sharded

    populate, put_and_cut = sharded._populate, sharded._DirectLeaf._put_and_cut
    with knobs.override_device_unpack(name in ("direct", "twice")):
        if name == "parent":
            sharded._populate = lambda src: None
        if name == "twice":
            sharded._DirectLeaf._put_and_cut = _put_twice_and_cut
        try:
            yield
        finally:
            sharded._populate = populate
            sharded._DirectLeaf._put_and_cut = put_and_cut


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=2147489201)
    parser.add_argument("--order", default="direct,twice,twice,direct",
                        help="the timed restores, repeated --rounds times")
    parser.add_argument("--rounds", type=int, default=3)
    parser.add_argument("--parent", type=int, default=0,
                        help="timed restores of the parent's path, at the end")
    parser.add_argument("--spans", type=int, default=1,
                        help="after the timed restores, one more a variant "
                        "with the program's spans on, summed by name")
    parser.add_argument("--out", default="chiprun_out/reshard_ab.jsonl")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)

    from chipbench import bench

    cell = bench.Cell(ROOT, CELL)
    if args.tiny:
        cell.config.update(TINY)
        cell.config["layer_types"] = cell.config["layer_types"][:2]
        cell.traffic["batch"] = [2, 16]
    snap_root, sink_fs = bench.make_sink("tmp" if args.tiny else "ram")
    try:
        import jax

        from torchsnapshot_tpu import Snapshot, obs
        from torchsnapshot_tpu.obs import tracer as program_tracer

        if not args.tiny:
            bench.enable_compile_cache(ROOT)
        devices = bench.pick_devices(cell.chips, allow_cpu=args.tiny)
        driver = bench.Driver(cell, args.seed, devices, snap_root)
        driver.make_state()
        state_bytes = driver.notes["state_bytes"]
        t0 = time.monotonic()
        driver.take()
        print(f"take {time.monotonic() - t0:.2f}s of {state_bytes} B on {sink_fs}",
              file=sys.stderr, flush=True)
        driver.drop()
        snap = driver.snapshots[-1]
        read_hbm = bench._bytes_in_use(devices)

        def counters():
            got = obs.metrics_snapshot()["counters"]
            return {k: got.get(k, 0) for k in (
                "reshard.host_alloc_bytes", "reshard.direct_bytes",
                "reshard.link_bytes", "reshard.handoff_bytes",
                "reshard.populate_refused", "exceptions.swallowed",
            )}

        os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
        out = open(os.path.join(ROOT, args.out), "w")
        runs = []

        def restore(name: str, index: int, check: bool, spans: bool = False) -> None:
            template = driver.rest.make(args.seed + 1 + index)
            app = driver._app(template, -1)
            want_layout = bench.state.layout_of(template)
            del template
            jax.block_until_ready(app["ts"].tree)
            before = counters()
            if spans:
                program_tracer.set_tracing(True)
                program_tracer.get_tracer().reset()
            with _variant(name), bench.MaxPoller(read_hbm, 0.02) as hbm:
                t0 = time.monotonic()
                Snapshot(snap["path"]).restore(app)
                tree = app["ts"].tree
                jax.block_until_ready(tree)
                wall = time.monotonic() - t0
            after = counters()
            by_name = {}
            if spans:
                program_tracer.set_tracing(False)
                for s in program_tracer.get_tracer().spans():
                    n, total = by_name.get(s.name, (0, 0.0))
                    by_name[s.name] = (n + 1, total + s.duration_ns / 1e9)
            if check:
                driver._judge(snap, tree, want_layout, app["meta"]["step"])
            fullest = bench.state.fullest_device_bytes(tree)
            record = {
                "variant": name, "index": index,
                "timed": not check and not spans,
                "restore_s": wall,
                "hbm_peak_x": hbm.max_seen / fullest if hbm.max_seen else None,
                **{k: after[k] - before[k] for k in after},
            }
            if spans:  # name -> [count, thread-seconds]
                record["spans"] = {
                    k: [n, round(t, 4)] for k, (n, t) in sorted(by_name.items())
                }
            runs.append(record)
            out.write(json.dumps(record) + "\n")
            out.flush()
            print(json.dumps(record), file=sys.stderr, flush=True)

        order = args.order.split(",")
        index = 0
        for name in dict.fromkeys(order):  # warm and checked, one a variant
            restore(name, index, check=True)
            index += 1
        for name in order * args.rounds + ["parent"] * args.parent:
            restore(name, index, check=False)
            index += 1
        for name in dict.fromkeys(order) if args.spans else ():
            restore(name, index, check=False, spans=True)
            index += 1
        out.close()
    finally:
        bench.remove_sink(snap_root)

    summary = {"state_bytes": state_bytes, "wrong": driver.wrong,
               "answers_checked": driver.answers_checked}
    for name in dict.fromkeys(r["variant"] for r in runs):
        walls = [r["restore_s"] for r in runs if r["variant"] == name and r["timed"]]
        peaks = [r["hbm_peak_x"] for r in runs if r["variant"] == name and r["hbm_peak_x"]]
        summary[name] = {
            "restore_s": walls,
            "median_s": statistics.median(walls) if walls else None,
            "hbm_peak_x": peaks,
        }
    print(json.dumps(summary), flush=True)
    return 0 if not any(driver.wrong.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
