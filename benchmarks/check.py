"""The comparison that decides ``correct``.

Runs once the window has closed, the answers have been read back and the
program is shut down.  Every number compared has a limit of its own;
``PERF.md`` section 2 gives the readings each was set from.

Exact guarantees (limit 0), over EVERY job the run finished and every
allocation in the store:

    failed_jobs           jobs of the window whose evaluation did not
                          read ``complete`` in time, or whose committed
                          allocations are not the ones asked
    placement_mismatch    |committed - asked| over all jobs, duplicate
                          allocation ids or names, allocations on unknown
                          nodes, nodes not ready at the end
    oversubscribed_nodes  nodes whose committed allocations do not fit
                          (``reference.oversubscribed_nodes``)
    late_commits          allocations committed at a raft index after
                          the one at which their evaluation read complete
    readback_mismatch     sampled ``GET /v1/allocation/<id>`` that differ
                          from the store
    redeliveries          broker nacks + failed worker batches + node
                          expiries, from boot

Scores (a sample of the window's jobs drawn from the seed, the slowest
among them; ``reference.check_plan``):

    score_gap      widest |score the program recorded for a pick - the
                   float64 reference's score of that node at that step|
    score_regret   widest gap by which a pick scores below the
                   reference's k-th best node at its step

Every cell holds every number above to its limit; no file can switch
one off.  Under ``--control <name>`` the two scores are those of what
``CONTROLS`` puts in the program's place on the same steps, held to the
same limits by the same comparison: such a run has to come out not
correct.  The program's own two are then printed only.
"""
from __future__ import annotations

import random

import numpy as np

import reference

LIMITS = {
    "failed_jobs": 0,
    "placement_mismatch": 0,
    "oversubscribed_nodes": 0,
    "late_commits": 0,
    "readback_mismatch": 0,
    "redeliveries": 0,
    "score_gap": 1.0e-3,
    "score_regret": 1.0e-3,
}


def _bfloat16(fleet: dict):
    import ml_dtypes

    return reference.Scorer(fleet, dtype=ml_dtypes.bfloat16)


# What ``--control`` puts in the program's place: the reference's scorer
# in the nearest precision below the float32 the configurations state,
# or the reference with a fault planted in it.
CONTROLS = {"bf16": _bfloat16, "worst_first": reference.WorstFirst}
REDELIVERY_COUNTERS = ("nomad.broker.nacks",
                       "nomad.workers.dispatch_failures",
                       "nomad.heartbeat.expiries")


def compare(seed: int, traffic: dict, fleet: dict, records: list,
            answers: dict, counters_end: dict, control_name: "str | None",
            say) -> tuple:
    """(checks {name: {"value", "limit"}}, info {name: value}).
    ``records``: one per finished job, ``in_window`` set on those that
    completed inside the window."""
    allocs, job_info = answers["allocs"], answers["jobs"]
    running = allocs["running"]
    live = {k: (v[running] if isinstance(v, np.ndarray)
                else [x for x, keep in zip(v, running) if keep])
            for k, v in allocs.items()}
    by_job: dict = {}
    for i, jid in enumerate(live["job"]):
        by_job.setdefault(jid, []).append(i)

    failed_jobs = mismatch = late = 0
    for r in records:
        jid = r["spec"]["id"]
        rows = by_job.get(jid, [])
        short = abs(len(rows) - r["spec"]["asked"])
        unknown = int((live["node"][rows] < 0).sum())
        if r["status"] != "complete" or short or unknown:
            failed_jobs += 1 if r["in_window"] else 0
            mismatch += short + unknown
        done_at = [m for eid, st, m in job_info.get(jid, {"evals": []})["evals"]
                   if eid == r["eval"] and st == "complete"]
        if done_at and rows:
            late += int((live["create_index"][rows] > done_at[0]).sum())
    mismatch += len(allocs["id"]) - len(set(allocs["id"]))
    names = list(zip(live["job"], live["name"]))
    mismatch += len(names) - len(set(names))
    mismatch += abs(len(fleet["ids"]) - answers["nodes_ready"])

    over, over_rows = reference.oversubscribed_nodes(fleet, live)
    if over_rows:
        say(f"reference: oversubscribed node rows {over_rows}")

    # Scores.
    rng = random.Random(f"{seed}:sample")
    done_ok = [r for r in records
               if r["in_window"] and r["status"] == "complete"]
    sample = []
    if done_ok:
        slowest = max(done_ok, key=lambda r: r["t_done"] - r["t_submit"])
        rest = [r for r in done_ok if r is not slowest]
        sample = [slowest] + rng.sample(
            rest, min(len(rest), int(traffic["check"]["sample_jobs"]) - 1))
    order = np.argsort(live["create_index"], kind="stable")
    sorted_index = live["create_index"][order]
    scorer = reference.Scorer(fleet)
    control = CONTROLS[control_name](fleet) if control_name else None
    gap = regret = c_gap = c_regret = 0.0
    plans = picks = no_score = c_unfit = 0
    detail = []
    for r in sample:
        rows = np.asarray(by_job.get(r["spec"]["id"], ()),
                          dtype=np.int64)
        if not len(rows):
            continue
        missing = int(np.isnan(live["score"][rows]).sum())
        if missing:
            no_score += missing
            continue
        reg_index = job_info[r["spec"]["id"]]["register_index"]
        for commit in np.unique(live["create_index"][rows]):
            res = reference.check_plan(
                fleet, r["spec"], live, order, sorted_index,
                rows[live["create_index"][rows] == commit],
                rows[live["create_index"][rows] < commit],
                reg_index, scorer, control)
            plans += 1
            picks += res["picks"]
            gap = max(gap, res["dscore"])
            regret = max(regret, res["regret"])
            if control is not None:
                c_gap = max(c_gap, res["control_dscore"])
                c_regret = max(c_regret, res["control_regret"])
                c_unfit += res["control_unfit"]
            detail.append({k: res[k] for k in (
                "dscore", "regret", "picks", "candidates", "excluded_nodes",
                "snapshot", "commit")})
    if sample and not plans:
        mismatch += 1   # nothing comparable among the sampled answers
    say(f"reference: {len(sample)} jobs sampled, {plans} plans, {picks} "
        f"picks compared, {no_score} picks without one recorded score")
    say("reference: widest score gaps "
        f"{sorted(detail, key=lambda d: -d['dscore'])[:3]}")

    values = {
        "failed_jobs": failed_jobs,
        "placement_mismatch": mismatch,
        "oversubscribed_nodes": over,
        "late_commits": late,
        "readback_mismatch": answers["readback_mismatch"],
        "redeliveries": sum(counters_end[k] for k in REDELIVERY_COUNTERS),
        "score_gap": gap,
        "score_regret": regret,
    }
    info = {"sampled_jobs": len(sample), "plans": plans, "picks": picks}
    if control is not None:
        info.update(program_score_gap=gap, program_score_regret=regret,
                    control_unfit_picks=c_unfit)
        values.update(score_gap=c_gap, score_regret=c_regret)
    checks = {k: {"value": values[k], "limit": LIMITS[k]} for k in LIMITS}
    return checks, info


def passed(checks: dict) -> bool:
    """The verdict: every number compared is inside its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())
