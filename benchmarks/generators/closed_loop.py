"""The one general traffic generator: ``clients`` closed loops.

Each client thread registers a job, waits until that job's evaluation is
terminal, and only then registers its next (callers that each wait for a
reply).  Everything a cell varies is a parameter of its traffic file:

    clients         threads in the loop
    job.groups      task groups per job (all with the same ask)
    job.count       copies per group
    job.ask         {cpu, memory_mb, mbits, dynamic_ports} of one copy
    job_timeout_s   a job not terminal by then counts as failed
    warmup          {"jobs_per_client": n}: the window opens once every
                    client has completed n jobs

The clock is the client's: it starts before the register call and stops
when the evaluation reads terminal.  Job k of client c is a function of
(seed, c, k) alone; every seed gives the same sizes, under other ids.
"""
from __future__ import annotations

import random
import threading
import time
import uuid


def job_spec(job: dict, seed: int, client: int, k: int) -> dict:
    """Job k of client c, from the traffic file's ``job`` and the seed."""
    rng = random.Random(f"{seed}:job:{client}:{k}")
    job_id = str(uuid.UUID(int=rng.getrandbits(128), version=4))
    count, n_groups = int(job["count"]), int(job["groups"])
    groups = [dict(job["ask"], name=f"tg-{g}", count=count)
              for g in range(n_groups)]
    return {"id": job_id, "name": f"bench-{client}-{k}", "type": "service",
            "groups": groups, "asked": count * n_groups}


def run(traffic: dict, seed: int, make_client, seconds: float,
        on_open, on_close, say) -> dict:
    """Drive the closed loops: warm up, hold the window open for
    ``seconds``, close it, let every client finish the job it has in
    flight.  ``make_client() -> (submit, wait_done)``; ``on_open`` /
    ``on_close`` run on this thread at the two edges of the window.
    Returns the edges (perf_counter) and one record per job."""
    n_clients = int(traffic["clients"])
    timeout = float(traffic["job_timeout_s"])
    warm_jobs = int(traffic["warmup"]["jobs_per_client"])
    stop = threading.Event()
    lock = threading.Lock()
    records: list = []
    done_count = [0] * n_clients
    errors: list = []

    def client_loop(c: int) -> None:
        try:
            submit, wait_done = make_client()
            k = 0
            while not stop.is_set():
                spec = job_spec(traffic["job"], seed, c, k)
                rec = {"client": c, "k": k, "spec": spec, "eval": ""}
                rec["t_submit"] = time.perf_counter()
                try:
                    rec["eval"] = submit(spec)
                    rec["status"] = wait_done(
                        rec["eval"], time.monotonic() + timeout)
                except Exception as e:  # a refused or broken call fails
                    rec["status"] = f"error: {type(e).__name__}: {e}"
                rec["t_done"] = time.perf_counter()
                with lock:
                    records.append(rec)
                    done_count[c] += 1
                k += 1
        except BaseException as e:
            errors.append(e)
            stop.set()

    threads = [threading.Thread(target=client_loop, args=(c,),
                                name=f"bench-client-{c}", daemon=True)
               for c in range(n_clients)]
    for t in threads:
        t.start()
    warm_deadline = time.monotonic() + timeout * max(1, warm_jobs)
    while min(done_count) < warm_jobs and not stop.is_set():
        if time.monotonic() > warm_deadline:
            stop.set()
            raise RuntimeError(f"warm-up: clients completed {done_count} "
                               f"jobs, want {warm_jobs} each")
        time.sleep(0.01)
    on_open()
    t_open = time.perf_counter()
    say(f"window open after warm-up jobs {done_count}")
    stop.wait(seconds)
    t_close = time.perf_counter()
    on_close()
    stop.set()
    for t in threads:
        t.join(timeout + 30.0)
    if errors:
        raise errors[0]
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a client did not finish its job in flight")
    return {"t_open": t_open, "t_close": t_close, "records": records}
