"""Open loop at an even rate: one job every ``1 / jobs_per_s`` seconds,
whether or not the one before has finished — users who submit on their
own schedule and do not wait for each other.  A pool of ``clients``
threads takes the jobs in order; each registers its job and waits for
the evaluation to be terminal.  Everything a cell varies is a parameter
of its traffic file:

    clients           threads in the pool (enough that a job starts
                      when it is due)
    job.type          the jobs' type (its scheduler queue and penalty)
    job.groups_cycle  task groups of job k: groups_cycle[k % len]
    job.groups        cap on the groups of a job   (a rehearsal lowers
    job.count         copies per group              these two)
    job.ask           {cpu, memory_mb} of one copy, the same for every
                      group; no network
    arrivals          jobs_per_s; late_start_warn_ms
    job_timeout_s     a job not terminal by then counts as failed
    warmup            {"jobs": n, "open_before_job_s": s}: n jobs
                      precede the window, which opens s seconds before
                      job n is due

The clock is the schedule's: a job's ``t_submit`` is the time it was
DUE, not when a client got to it, so a stall is charged to the jobs it
delayed.  The generator says how late the starts ran (median, p99,
largest).  Job k is a function of (seed, k) alone; every seed offers
the same shapes under other ids.
"""
from __future__ import annotations

import queue
import random
import threading
import time
import uuid


def job_spec(job: dict, seed: int, k: int) -> dict:
    """Job k, from the traffic file's ``job`` and the seed."""
    rng = random.Random(f"{seed}:job:{k}")
    job_id = str(uuid.UUID(int=rng.getrandbits(128), version=4))
    cycle = job["groups_cycle"]
    n_groups = min(int(cycle[k % len(cycle)]), int(job["groups"]))
    count = int(job["count"])
    groups = [dict(job["ask"], name=f"t{g:02d}", count=count)
              for g in range(n_groups)]
    return {"id": job_id, "name": f"dag-{k}", "type": job["type"],
            "groups": groups, "asked": count * n_groups}


def _rank(sorted_values: list, q: float) -> float:
    return sorted_values[min(len(sorted_values) - 1,
                             int(q * len(sorted_values)))]


def run(traffic: dict, seed: int, make_client, seconds: float,
        on_open, on_close, say) -> dict:
    """Offer the jobs: warm up, hold the window open for ``seconds``,
    close it, offer nothing more, let the pool finish what was offered.
    ``make_client() -> (submit, wait_done)``; ``on_open`` / ``on_close``
    run on this thread at the two edges of the window.  Returns the
    edges (perf_counter) and one record per job."""
    job = traffic["job"]
    period = 1.0 / float(traffic["arrivals"]["jobs_per_s"])
    timeout = float(traffic["job_timeout_s"])
    warm_jobs = int(traffic["warmup"]["jobs"])
    lead = float(traffic["warmup"]["open_before_job_s"])
    work: queue.Queue = queue.Queue()
    stop = threading.Event()
    lock = threading.Lock()
    records: list = []
    errors: list = []

    def client_loop() -> None:
        try:
            submit, wait_done = make_client()
            ready.release()
            while True:
                rec = work.get()
                if rec is None:
                    return
                rec["t_start"] = time.perf_counter()
                try:
                    rec["eval"] = submit(rec["spec"])
                    rec["status"] = wait_done(
                        rec["eval"], time.monotonic() + timeout)
                except Exception as e:  # a refused or broken call fails
                    rec["status"] = f"error: {type(e).__name__}: {e}"
                rec["t_done"] = time.perf_counter()
                with lock:
                    records.append(rec)
        except BaseException as e:
            errors.append(e)
            stop.set()

    def offer_loop(t_first: float) -> None:
        """Job k is due at t_first + k * period; none after ``stop``."""
        k = 0
        while True:
            due = t_first + k * period
            rec = {"k": k, "eval": "", "t_submit": due,
                   "spec": job_spec(job, seed, k)}
            if stop.wait(max(0.0, due - time.perf_counter())):
                return
            work.put(rec)
            k += 1

    ready = threading.Semaphore(0)
    threads = [threading.Thread(target=client_loop, daemon=True,
                                name=f"bench-client-{c}")
               for c in range(int(traffic["clients"]))]
    for t in threads:
        t.start()
    for _ in threads:
        if not ready.acquire(timeout=60.0) or errors:
            stop.set()
            raise errors[0] if errors else RuntimeError(
                "a client did not come up")
    t_first = time.perf_counter() + 0.05
    offerer = threading.Thread(target=offer_loop, args=(t_first,),
                               name="bench-offer", daemon=True)
    offerer.start()
    stop.wait(max(0.0, t_first + warm_jobs * period - lead
                  - time.perf_counter()))
    with lock:
        warm_done = len(records)
    on_open()
    t_open = time.perf_counter()
    say(f"window open: {warm_done} of {warm_jobs} warm-up jobs terminal; "
        f"job {warm_jobs} is due in "
        f"{t_first + warm_jobs * period - t_open:.3f}s")
    stop.wait(seconds)
    t_close = time.perf_counter()
    stop.set()      # no job after the window, however long on_close takes
    on_close()
    offerer.join(30.0)
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join(timeout + 30.0)
    if errors:
        raise errors[0]
    if offerer.is_alive() or any(t.is_alive() for t in threads):
        raise RuntimeError("a client did not finish the jobs offered")
    late = sorted(1e3 * (r["t_start"] - r["t_submit"]) for r in records)
    if late:
        say(f"lateness of starts over {len(late)} jobs: median "
            f"{_rank(late, 0.5):.1f} ms, p99 {_rank(late, 0.99):.1f} ms, "
            f"largest {late[-1]:.1f} ms")
        if late[-1] > float(traffic["arrivals"]["late_start_warn_ms"]):
            say(f"LATE: a job started {late[-1]:.1f} ms after it was due "
                "(the pool was busy or the host starved the generator): "
                "the wait is in its latency")
    return {"t_open": t_open, "t_close": t_close, "records": records}
