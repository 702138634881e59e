"""Share, in percent, of the window's ``sched.dispatch`` lanes that the
plain reference cannot score: lanes planned with more than one top-k
round (``rounds`` > 1: some slot had more copies than nodes with room)
or on the sequence kernel (``mode`` = ``sequence``).
``reference.check_plan`` scores all picks of a slot before it applies
any of them, so a slot placed over two rounds reads one anti-affinity
penalty off (PERF.md section 7).  A sound run of a cell sized for the
reference reads 0.  Lanes without the tags are left out; with none left
the reader returns nothing.  Parameters: none."""


def reduce(params: dict, ctx: dict):
    lanes = [tags for s in ctx["spans"] if s["name"] == "sched.dispatch"
             and "mode" in (tags := s.get("tags") or {})]
    if not lanes:
        return None
    multi = sum(1 for t in lanes
                if t["mode"] == "sequence" or t.get("rounds", 1) > 1)
    ctx["notes"].append(
        f"sched.dispatch: {len(lanes)} tagged lanes, {multi} of them over "
        "more than one round or on the sequence kernel; engines "
        f"{sorted({t.get('engine') for t in lanes})}")
    return 100.0 * multi / len(lanes)
