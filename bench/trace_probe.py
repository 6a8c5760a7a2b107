"""What the split spans show in a cell: one JSON line per seed.

Runs a cell's harness once per seed in one process, as ``bench/run.py``
does but without its result line, and prints the end-to-end metrics of
the window and whether the comparison held.  With ``--trace 1`` the line
also holds every per-layer metric of the cell and the readers of the split
spans (:data:`SPLIT`), the fit of span time onto the device trace's clock
(``clock``: its residuals over the ``bench.*`` twins, in us), how much of
each ``codec.pack``/``codec.unpack`` span its child spans cover
(``coverage``), the wire's self time beside ``wire.send`` + ``wire.recv``
+ ``queued_s`` (``wire``), and the longest idle gaps of the device named by
the program spans open across them (``gaps``).

    python3 bench/trace_probe.py --workload <cell> --seeds 11,12,13 --seconds 51 --trace 1

The benchmark's own runs never run this.  It exits non-zero without a TPU.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

from fdbbench import clock  # noqa: E402
from fdbbench.harness import END_TO_END, NoDevice, Readings, compare, measure, require_tpu  # noqa: E402
from fdbbench.spans import duration, union_seconds  # noqa: E402
from fdbbench.spec import load_cell, load_reader  # noqa: E402

#: readers of the codec's child spans and the wire's split
SPLIT = ("codec_host_s_per_GB.pack", "codec_host_s_per_GB.unpack", "codec_launch_ms.pack",
         "codec_launch_ms.unpack", "wire_io_s_per_GB.archive", "wire_io_s_per_GB.retrieve",
         "serve_queue_ms_per_request")


def coverage(spans: list[dict], parent: str) -> dict:
    """Share of the ``parent`` spans' time their children cover: over all
    of them, and the least of any one."""
    kids: dict[int, float] = {}
    for s in spans:
        if s["name"].startswith(parent + ".") and s.get("parent_id") is not None:
            kids[s["parent_id"]] = kids.get(s["parent_id"], 0.0) + duration(s)
    found = [s for s in spans if s["name"] == parent]
    if not found:
        return {}
    shares = [kids.get(s["span_id"], 0.0) / duration(s) for s in found if duration(s) > 0]
    return {"spans": len(found),
            "share": sum(kids.get(s["span_id"], 0.0) for s in found) / sum(map(duration, found)),
            "least": min(shares, default=None)}


def wire_split(spans: list[dict], prefix: str) -> dict:
    """The self time of the wire spans named ``prefix*`` (less their
    ``server.*`` children) beside their ``wire.send``, ``wire.recv`` and
    their server spans' ``queued_s``; ``over`` counts the spans whose three
    parts exceed their self time."""
    by_parent: dict[int, list[dict]] = {}
    for s in spans:
        if s.get("parent_id") is not None:
            by_parent.setdefault(s["parent_id"], []).append(s)
    tot = {"self_s": 0.0, "send_s": 0.0, "recv_s": 0.0, "queued_s": 0.0}
    over, worst, n = 0, 0.0, 0
    for w in spans:
        if not w["name"].startswith(prefix):
            continue
        n += 1
        kids = by_parent.get(w["span_id"], [])
        served = [k for k in kids if k["name"].startswith("server.")]
        own = duration(w) - union_seconds(
            (max(k["t0"], w["t0"]), min(k["t1"], w["t1"])) for k in served if k["t1"] > k["t0"])
        send = sum(duration(k) for k in kids if k["name"] == "wire.send")
        recv = sum(duration(k) for k in kids if k["name"] == "wire.recv")
        queued = sum(k.get("attrs", {}).get("queued_s", 0.0) for k in served)
        tot["self_s"] += own
        tot["send_s"] += send
        tot["recv_s"] += recv
        tot["queued_s"] += queued
        excess = send + recv + queued - own
        if excess > 0:
            over += 1
            worst = max(worst, excess)
    tot["wait_s"] = tot["self_s"] - tot["send_s"] - tot["recv_s"] - tot["queued_s"]
    return {"spans": n, **tot, "over": over, "worst_excess_s": worst}


def traced(o, root: Path) -> dict:
    ctx = Readings(o)
    per_layer = {}
    for name in [m["name"] for m in o.cell.per_layer] + list(SPLIT):
        per_layer[name] = load_reader(root, name)(ctx)
    s, a, fit = clock.twins(ctx.spans, ctx.device)
    r = np.abs(a - fit(s)) / 1e3
    return {
        "per_layer": per_layer,
        "clock": {"twins": len(s), "pairs": fit.pairs, "ns_per_s": fit.ns_per_s,
                  "median_abs_residual_us": float(np.median(r)),
                  "p99_abs_residual_us": float(np.percentile(r, 99)),
                  "max_abs_residual_us": float(r.max())},
        "coverage": {k: coverage(ctx.spans, f"codec.{k}") for k in ("pack", "unpack")},
        "wire": {k: wire_split(ctx.spans, p) for k, p in
                 (("archive", "wire.archive_batch"), ("retrieve", "wire.retrieve"))},
        "gaps": clock.gap_spans(ctx.device, ctx.spans, 10, fit),
        "busy_s": ctx.device.busy_s(),
        "window_s": ctx.device.window_s,
        "server_spans": sum(sp["proc"] == "server" for sp in ctx.spans),
    }


def main(argv, *, root=ROOT, device_check=require_tpu) -> int:
    ap = argparse.ArgumentParser(prog="bench/trace_probe.py", description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    cell = load_cell(root, args.workload)
    try:
        devices = device_check(cell.chips)
    except NoDevice as e:
        print(f"trace_probe: {e}", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        o = measure(cell, seed, args.seconds, trace=bool(args.trace), devices=devices,
                    t_start=time.perf_counter())
        checks = compare(o)
        line = {"workload": cell.name, "seed": seed, "trace": args.trace,
                "correct": all(v <= lim for v, lim in checks.values()),
                "end_to_end": {m["name"]: END_TO_END[m["name"]](o) for m in cell.end_to_end
                               if m["name"] != "setup_s"}}
        if args.trace:
            line.update(traced(o, root))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
