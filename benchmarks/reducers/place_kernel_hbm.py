"""The fused placement windows on the device plane.  ``what`` =
``device_ms``: the mean DEVICE time, in ms, of a window's module event
(the ``device.dispatch`` span around it also holds the upload and the
fetch; the note gives both).  ``what`` = ``hbm_share``: the share, in
percent, of the device's memory bandwidth that the windows reached: the
bytes a window has to move
(``kernel_bytes.fused_rounds_window``, from the shape tags of its
``device.dispatch`` span) over the DEVICE time of the module event it
pairs with, over the chip's published bytes a second (``peaks.json``,
by ``device_kind``; a chip that is not in the table is an error).

Pairing is ``idle_attribution``'s: inside the traced slice the k-th
``device.dispatch`` span of a program is the k-th module event
``jit_<program>``.  The windows are the spans that carry every one
of ``tags`` (``b_pad``: only the fused sites state a lane bucket;
``k_cap`` and ``rounds``: only the top-k rounds programs).  The share is
total bytes over total device time of the paired windows; the note
gives the windows, their mean device time against their mean span, and
the bytes.  No paired window in the slice (no device trace, no fused
window on the device, a program with no such span): nothing.
Parameters: ``what``, ``tags``, ``peaks`` (a file beside ``run.py``)."""
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reduce(params: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("modules") or not trace.get("slice_perf"):
        return None
    pairing = _load(os.path.join(BENCH, "reducers", "idle_attribution.py"),
                    "bench_pairing")
    kernel_bytes = _load(os.path.join(BENCH, "kernel_bytes.py"),
                         "bench_kernel_bytes")
    lo, hi = (t - ctx["span_clock_offset"] for t in trace["slice_perf"])
    spans = {}
    for s in ctx["spans"]:
        tags = s.get("tags") or {}
        if s["name"] == "device.dispatch" and lo <= s["t0"] <= hi \
                and all(t in tags for t in params["tags"]):
            spans.setdefault(tags.get("program"), []).append(s)
    events = {}
    for ev in trace["modules"]:
        events.setdefault(pairing.program_of(ev[0]), []).append(ev)
    pairs = []
    for program, ss in spans.items():
        pairs += pairing.pair_up(
            sorted(ss, key=lambda s: s["t0"]),
            sorted(events.get(program, []), key=lambda e: e[1]))
    if not pairs:
        return None
    moved = sum(kernel_bytes.fused_rounds_window(s["tags"])
                for s, _e in pairs)
    device_s = sum(e[2] for _s, e in pairs)
    span_s = sum(s["dur"] for s, _e in pairs)
    if params["what"] == "device_ms":
        return 1e3 * device_s / len(pairs)
    import jax

    with open(os.path.join(BENCH, params["peaks"])) as fh:
        peak = json.load(fh)[jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    ctx["notes"].append(
        f"fused placement windows in the slice: {len(pairs)} paired of "
        f"{sum(map(len, spans.values()))} spans; mean device time "
        f"{1e3 * device_s / len(pairs):.3f} ms of a mean span of "
        f"{1e3 * span_s / len(pairs):.3f} ms; {moved} bytes to move; "
        f"lanes {sorted({s['tags']['lanes'] for s, _e in pairs})}")
    if device_s <= 0:
        return None
    return 100.0 * moved / device_s / peak
