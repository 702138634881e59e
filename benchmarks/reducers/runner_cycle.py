"""The fused runner's cycle, from its ``worker.batch`` spans (one per
batch: the dequeue call that returned the batch -> its last ack, tagged
``cpu_s``, the runner thread's CPU seconds after the dequeue) and their
``worker.dequeue`` children (the wait for work).  ``what`` picks the
number, in percent:

- ``busy_share``: (sum of batches - sum of their dequeues) / (first
  batch's start -> last batch's end).  Near 100: the one runner is the
  bottleneck.  Well under: work waits somewhere before the broker.
- ``on_cpu_share``: sum of ``cpu_s`` / (sum of batches - sum of their
  dequeues).  Well under 100: the runner mostly waits while it holds a
  batch (plan results, the interpreter lock), it does not compute.
"""


def reduce(params: dict, ctx: dict):
    batches = {s["span_id"]: s for s in ctx["spans"]
               if s["name"] == "worker.batch" and "span_id" in s}
    if not batches:
        return None
    waited = dict.fromkeys(batches, 0.0)
    for s in ctx["spans"]:
        if s["name"] == "worker.dequeue" and s.get("parent_id") in waited:
            waited[s["parent_id"]] += s["dur"]
    held = sum(b["dur"] - waited[i] for i, b in batches.items())
    if params["what"] == "on_cpu_share":
        if held <= 0:
            return None
        cpu = sum((b.get("tags") or {}).get("cpu_s", 0.0)
                  for b in batches.values())
        return 100.0 * cpu / held
    if params["what"] != "busy_share":
        raise ValueError(f"runner_cycle: unknown what={params['what']!r}")
    extent = max(b["t0"] + b["dur"] for b in batches.values()) \
        - min(b["t0"] for b in batches.values())
    if extent <= 0:
        return None
    ctx["notes"].append(
        f"runner: {len(batches)} batches over {extent:.3f}s, holding a "
        f"batch {held:.3f}s, waiting for one "
        f"{sum(waited.values()):.3f}s")
    return 100.0 * held / extent
