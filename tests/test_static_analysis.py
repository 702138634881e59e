"""Tier-1 gate for the static analyzers + runtime sanitizers.

Three layers, mirroring the reference's `go vet` + `go test -race` CI
discipline (reference scripts/test.sh:12-13):

1. **The standing gate**: `nomad-tpu lint` over the real package must be
   clean — zero unallowlisted findings, zero stale allowlist entries,
   every allowlist line justified.
2. **Analyzer unit tests** on synthetic packages: each rule (bare-write,
   lock-cycle, nested-self-acquire, impure-call, concretize,
   traced-branch, static-arg exemptions) proves it fires — a lint that
   cannot fail gates nothing.
3. **Runtime sanitizers** cross-checking the static results: the
   lock-order witness observes real acquisition chains through a real
   EvalBroker/plan-queue workload (cycles fail), and the recompile
   sentinel fails a kernel retracing past its budget.
"""
from __future__ import annotations

import os
import textwrap
import threading
import time

import pytest

from nomad_tpu.analysis import (
    Finding,
    default_allowlist_path,
    load_allowlist,
    partition_findings,
    run_lint,
)
from nomad_tpu.analysis import jaxlint, lockcheck
from nomad_tpu.analysis.sanitizers import (
    DEFAULT_BUDGET,
    LockOrderWitness,
    RecompileSentinel,
)

_PACKAGE_LINT: list = []


def package_lint() -> list:
    """``run_lint(strict=True)`` over the real package, computed once
    per session: the tree does not change while the suite runs, and the
    dozen "rides the gates" tests below each paid the whole ~5 s pass
    for the same findings — a minute of a tier-1 gate that is already
    kill-bound (ROADMAP D5).  ``test_package_is_clean`` and the timing
    budget test still run the real pass themselves."""
    if not _PACKAGE_LINT:
        _PACKAGE_LINT.append(run_lint(strict=True))
    return list(_PACKAGE_LINT[0])


_PACKAGE_GRAPH: list = []


def package_graph():
    """The real package's interprocedural call graph, built once per
    session for the same reason.  The passes share one graph inside
    ``run_lint`` too, so handing the gate tests a shared instance asks
    nothing new of them."""
    from nomad_tpu.analysis import default_package_root
    from nomad_tpu.analysis.callgraph import CallGraph

    if not _PACKAGE_GRAPH:
        _PACKAGE_GRAPH.append(CallGraph.build(default_package_root()))
    return _PACKAGE_GRAPH[0]


def write_pkg(tmp_path, name, source) -> str:
    d = tmp_path / name
    d.mkdir(exist_ok=True)
    (d / "mod.py").write_text(textwrap.dedent(source))
    return str(d)


# ---------------------------------------------------------------------------
# 1. the standing gate
# ---------------------------------------------------------------------------

class TestLintGate:
    def test_package_is_clean(self):
        """THE gate: every finding over nomad_tpu/ is fixed or carries a
        justified allowlist line, and no allowlist line is stale."""
        allowlist = load_allowlist(default_allowlist_path())
        findings = run_lint(strict=True)
        gating, allowed, stale = partition_findings(findings, allowlist)
        assert not gating, "unallowlisted findings:\n" + "\n".join(
            f.render() for f in gating)
        assert not stale, f"stale allowlist entries (remove them): {stale}"

    def test_every_allowlist_entry_is_justified(self):
        # load_allowlist raises on an unjustified line; also sanity-check
        # the parsed justifications are real sentences, not "x".
        allowlist = load_allowlist(default_allowlist_path())
        for key, why in allowlist.items():
            assert len(why) > 10, f"throwaway justification for {key}"

    def test_unjustified_entry_rejected(self, tmp_path):
        p = tmp_path / "allow.txt"
        p.write_text("bare-write:a.py:C.x\n")
        with pytest.raises(ValueError, match="justification"):
            load_allowlist(str(p))

    def test_cli_lint_runs_clean(self, capsys):
        from nomad_tpu.cli.main import main

        assert main(["lint"]) == 0
        out = capsys.readouterr().out
        assert "0 finding(s)" in out

    def test_stale_allowlist_entry_gates(self):
        findings = [Finding("bare-write", "a.py", "C.x", "m")]
        gating, allowed, stale = partition_findings(
            findings, {"bare-write:a.py:C.x": "ok",
                       "bare-write:gone.py:D.y": "fixed long ago"})
        assert not gating and len(allowed) == 1
        assert stale == ["bare-write:gone.py:D.y"]

    def test_whole_program_pass_fits_timing_budget(self):
        """The interprocedural passes run on every tier-1 invocation;
        they must stay well under 10s on tier-1 hardware or the gate
        becomes the bottleneck it polices.  The consensus-plane passes
        (PR 16) ride the same budget: whole-program lint including the
        apply-determinism closure, the fencing fixpoint, and the
        endpoint contract table measured ~5s at introduction."""
        import time as _time

        start = _time.monotonic()
        run_lint(strict=True)
        elapsed = _time.monotonic() - start
        assert elapsed < 10.0, f"full lint took {elapsed:.1f}s (>10s)"

    def test_lint_json_reports_self_coverage(self, capsys):
        """Call-graph blind spots (dynamic call sites the passes cannot
        follow) are REPORTED, not silent (-json coverage block)."""
        import json as _json

        from nomad_tpu.cli.main import main

        assert main(["lint", "-json"]) == 0
        doc = _json.loads(capsys.readouterr().out)
        cov = doc["coverage"]
        assert cov["functions"] > 0 and cov["call_sites"] > 0
        assert cov["dynamic"] > 0          # blind spots exist...
        assert 0 < cov["resolved_fraction"] <= 1.0  # ...and are counted
        assert set(doc) >= {"gating", "advisory", "allowlisted",
                            "stale_allowlist", "coverage"}

    def test_changed_mode_filters_to_touched_files(self, tmp_path,
                                                   capsys):
        """`nomad-tpu lint -changed REV` reports only findings in files
        git says were touched since REV."""
        import subprocess

        from nomad_tpu.cli.main import main

        def git(*args):
            subprocess.run(["git", "-C", str(tmp_path), *args],
                           check=True, capture_output=True,
                           env={"GIT_AUTHOR_NAME": "t",
                                "GIT_AUTHOR_EMAIL": "t@t",
                                "GIT_COMMITTER_NAME": "t",
                                "GIT_COMMITTER_EMAIL": "t@t",
                                "HOME": str(tmp_path),
                                "PATH": os.environ.get("PATH", "")})

        pkg = tmp_path / "pkg"
        pkg.mkdir()
        clean = "def ok():\n    return 1\n"
        bad = textwrap.dedent("""
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0
                def inc(self):
                    with self._lock:
                        self.n += 1
                def bad(self):
                    self.n = 0
        """)
        (pkg / "untouched.py").write_text(bad)
        (pkg / "touched.py").write_text(clean)
        git("init", "-q")
        git("add", "-A")
        git("commit", "-qm", "base")
        # Introduce the SAME defect in the touched file only.
        (pkg / "touched.py").write_text(bad.replace("class C",
                                                    "class D"))
        rc = main(["lint", str(pkg), "-changed", "HEAD",
                   "-allowlist", str(tmp_path / "none.txt")])
        out = capsys.readouterr().out
        assert rc == 1
        assert "touched.py" in out
        assert "untouched.py" not in out, \
            "changed-mode must filter pre-existing findings"

    def test_group_commit_paths_ride_the_gates(self):
        """ISSUE 5 satellite: the group-commit window pass
        (ops/plan_conflict.py) and the FSM batch-apply path are inside
        every gate's scan set — tracer lint, lockcheck and the
        interprocedural passes — with zero findings and no allowlist
        entries of their own."""
        from nomad_tpu.analysis import (default_package_root,
                                        load_allowlist)
        from nomad_tpu.analysis.callgraph import CallGraph

        pkg = default_package_root()
        graph = package_graph()
        assert any(q.startswith("nomad_tpu.ops.plan_conflict:")
                   for q in graph.functions), \
            "plan_conflict.py missing from the interprocedural graph"
        assert "nomad_tpu.server.fsm:NomadFSM._apply_plan_batch" in \
            graph.functions, "fsm batch path missing from the graph"
        assert "nomad_tpu.state.store:StateStore.upsert_allocs_batched" \
            in graph.functions

        findings = package_lint()
        touching = [f for f in findings
                    if "plan_conflict" in f.path
                    or "_apply_plan_batch" in f.render()
                    or "upsert_allocs_batched" in f.render()]
        assert touching == [], "group-commit paths must lint clean:\n" \
            + "\n".join(f.render() for f in touching)
        allow = load_allowlist(default_allowlist_path())
        assert not any("plan_conflict" in e or "_apply_plan_batch" in e
                       or "upsert_allocs_batched" in e
                       for e in allow), \
            "group-commit paths must not need allowlist entries"

    def test_overload_plane_rides_the_gates(self):
        """ISSUE 6 satellite: the overload control plane
        (server/overload.py) and the TTL wheel (server/ttlwheel.py +
        the rewritten heartbeat manager) are inside every gate's scan
        set — blocking-under-lock, lock-order, and thread-lifecycle
        passes — with zero findings and no allowlist entries of their
        own."""
        from nomad_tpu.analysis import (default_package_root,
                                        load_allowlist)
        from nomad_tpu.analysis.callgraph import CallGraph

        pkg = default_package_root()
        graph = package_graph()
        for qual in (
            "nomad_tpu.server.overload:OverloadController.admit",
            "nomad_tpu.server.overload:TokenBucket.try_take",
            "nomad_tpu.server.ttlwheel:TTLWheel.arm",
            "nomad_tpu.server.ttlwheel:TTLWheel._run",
            "nomad_tpu.server.heartbeat:"
            "HeartbeatManager._reconcile_loop",
            "nomad_tpu.server.heartbeat:HeartbeatManager._on_ttl_expire",
        ):
            assert qual in graph.functions, \
                f"{qual} missing from the interprocedural graph"

        findings = package_lint()
        touching = [f for f in findings
                    if "overload" in f.path or "ttlwheel" in f.path
                    or "heartbeat" in f.path]
        assert touching == [], \
            "overload plane must lint clean:\n" + \
            "\n".join(f.render() for f in touching)
        allow = load_allowlist(default_allowlist_path())
        assert not any("server/overload" in e or "server/ttlwheel" in e
                       or "server/heartbeat" in e for e in allow), \
            "overload plane must not need allowlist entries"

    def test_serving_plane_rides_the_gates(self):
        """ISSUE 7 satellite: the event-driven serving plane —
        selector mux + dispatch pool (server/mux.py), the rewritten
        RPCServer/MuxConn (server/rpc.py), the watch fan-out
        (state/store.py), the event-driven HTTP edge
        (agent/http_server.py) and the agent swarm (agent/swarm.py) —
        is inside every gate's scan set, strict-clean, with zero
        allowlist entries of its own (the refactor RETIRED the
        _serve_mux thread-leak and MuxConn._wlock blocking waivers)."""
        from nomad_tpu.analysis.callgraph import CallGraph
        from nomad_tpu.analysis import default_package_root

        pkg = default_package_root()
        graph = package_graph()
        for qual in (
            "nomad_tpu.server.mux:EdgeLoop._run",
            "nomad_tpu.server.mux:EdgeLoop._close",
            "nomad_tpu.server.mux:DispatchPool.submit",
            "nomad_tpu.server.mux:DispatchPool._run",
            "nomad_tpu.server.rpc:RPCServer._execute",
            "nomad_tpu.server.rpc:RPCServer._park",
            "nomad_tpu.server.rpc:MuxConn.call_async",
            "nomad_tpu.server.rpc:MuxConn._write_loop",
            "nomad_tpu.state.store:StateWatch.subscribe",
            "nomad_tpu.state.store:StateWatch.notify",
            "nomad_tpu.agent.swarm:AgentSwarm._issue_poll",
            "nomad_tpu.agent.http_server:HTTPServer._serve_one",
        ):
            assert qual in graph.functions, \
                f"{qual} missing from the interprocedural graph"

        allowlist = load_allowlist(default_allowlist_path())
        gating, _allowed, _stale = partition_findings(
            package_lint(), allowlist)
        touching = [f for f in gating
                    if "server/mux" in f.path or "agent/swarm" in f.path
                    or "server/rpc" in f.path
                    or "state/store" in f.path
                    or "agent/http_server" in f.path]
        assert touching == [], \
            "serving plane must lint clean:\n" + \
            "\n".join(f.render() for f in touching)
        allow = load_allowlist(default_allowlist_path())
        assert not any("server/mux" in e or "agent/swarm" in e
                       for e in allow), \
            "serving plane must not need allowlist entries"
        assert not any("_serve_mux" in e or "_wlock" in e
                       for e in allow), \
            "the retired rpc.py waivers must stay retired"

    def test_crash_recovery_paths_ride_the_gates(self):
        """ISSUE 8 satellite: the durability & crash-recovery plane —
        CRC-framed FileLogStore (tail-scan, power-loss simulation),
        checksummed SnapshotStore, MetaStore, and the CrashHarness —
        is inside every gate's scan set, strict-clean, with zero
        allowlist entries of its own."""
        from nomad_tpu.analysis import (default_package_root,
                                        load_allowlist)
        from nomad_tpu.analysis.callgraph import CallGraph

        pkg = default_package_root()
        graph = package_graph()
        for qual in (
            "nomad_tpu.server.raft:FileLogStore.append",
            "nomad_tpu.server.raft:FileLogStore._scan_and_recover",
            "nomad_tpu.server.raft:FileLogStore._power_loss",
            "nomad_tpu.server.raft:FileLogStore._recover_tail",
            "nomad_tpu.server.raft:SnapshotStore.save",
            "nomad_tpu.server.raft:SnapshotStore._read_verified",
            "nomad_tpu.server.raft:MetaStore.save",
            "nomad_tpu.faultinject.crash:CrashHarness.kill",
            "nomad_tpu.faultinject.crash:CrashHarness.reboot",
            "nomad_tpu.faultinject.crash:freeze_storage",
            "nomad_tpu.server.server:Server.abandon",
            "nomad_tpu.state.store:_ReadMixin.fingerprint",
        ):
            assert qual in graph.functions, \
                f"{qual} missing from the interprocedural graph"

        allowlist = load_allowlist(default_allowlist_path())
        gating, _allowed, _stale = partition_findings(
            package_lint(), allowlist)
        touching = [f for f in gating
                    if "server/raft" in f.path
                    or "faultinject/crash" in f.path]
        assert touching == [], \
            "crash-recovery plane must lint clean:\n" + \
            "\n".join(f.render() for f in touching)
        assert not any("faultinject/crash" in e or "_power_loss" in e
                       or "_scan_and_recover" in e or "MetaStore" in e
                       for e in allowlist), \
            "crash-recovery plane must not need allowlist entries"

    def test_sharded_fleet_paths_ride_the_gates(self):
        """ISSUE 12 satellite: the first-class sharding plane — the
        mesh-resolution authority (parallel/mesh.dispatch_mesh), the
        unified ShardedResidency, the sharded single-eval dispatch,
        and the columnar node table (structs/node_slab.py + the store
        bulk path) — is inside every gate's scan set, strict-clean,
        and the touched models/ modules carry ZERO allowlist entries:
        the three UsageMirror double-checked-read waivers are retired
        (sync/sync_net now fence under the mirror lock) and must stay
        retired."""
        from nomad_tpu.analysis import (default_allowlist_path,
                                        default_package_root,
                                        load_allowlist)
        from nomad_tpu.analysis.callgraph import CallGraph

        pkg = default_package_root()
        graph = package_graph()
        for qual in (
            "nomad_tpu.parallel.mesh:dispatch_mesh",
            "nomad_tpu.models.fleet:ShardedResidency.install",
            "nomad_tpu.models.fleet:UsageMirror.device_usage_sharded",
            "nomad_tpu.models.fleet:UsageMirror.sync",
            "nomad_tpu.models.fleet:_build_fleet_slab",
            "nomad_tpu.scheduler.jax_binpack:"
            "JaxBinPackScheduler._dispatch_device_sharded",
            "nomad_tpu.structs.node_slab:NodeSlab.node",
            "nomad_tpu.structs.node_slab:node_slab_of",
            "nomad_tpu.state.store:StateStore.upsert_node_slab",
        ):
            assert qual in graph.functions, \
                f"{qual} missing from the interprocedural graph"

        allowlist = load_allowlist(default_allowlist_path())
        gating, _allowed, _stale = partition_findings(
            package_lint(), allowlist)
        touching = [f for f in gating
                    if "parallel/" in f.path or "models/" in f.path
                    or "node_slab" in f.path]
        assert touching == [], \
            "sharding plane must lint clean:\n" + \
            "\n".join(f.render() for f in touching)
        assert not any("models/" in e or "parallel/" in e
                       or "node_slab" in e for e in allowlist), \
            "models/ + parallel/ must carry zero allowlist entries " \
            "(the UsageMirror waivers are retired)"

    def test_columnar_paths_ride_the_gates(self):
        """ISSUE 9 satellite: the columnar alloc contract — the
        AllocSlab/SlabAlloc module (structs/alloc_slab.py), the
        scheduler's columnar native-args path, the slab-aware fleet
        readers, and the FSM's columnar wire decode — is inside every
        gate's scan set, strict-clean, with zero allowlist entries of
        its own."""
        from nomad_tpu.analysis import default_package_root
        from nomad_tpu.analysis.callgraph import CallGraph

        pkg = default_package_root()
        graph = package_graph()
        for qual in (
            "nomad_tpu.structs.alloc_slab:AllocSlab.wire",
            "nomad_tpu.structs.alloc_slab:AllocSlab.from_wire",
            "nomad_tpu.structs.alloc_slab:AllocSlab.task_resources_of",
            "nomad_tpu.structs.alloc_slab:AllocSlab.patch_row",
            "nomad_tpu.structs.alloc_slab:SlabAlloc.copy",
            "nomad_tpu.structs.alloc_slab:SlabWireEncoder.encode_list",
            "nomad_tpu.structs.alloc_slab:_slab_fill",
            "nomad_tpu.structs.alloc_slab:slab_ref",
            "nomad_tpu.structs.alloc_slab:decode_alloc_list",
            "nomad_tpu.scheduler.jax_binpack:"
            "JaxBinPackScheduler._finish_native_args",
            "nomad_tpu.server.fsm:NomadFSM._apply_alloc_update",
            "nomad_tpu.models.fleet:alloc_vec",
            "nomad_tpu.models.fleet:_net_row",
        ):
            assert qual in graph.functions, \
                f"{qual} missing from the interprocedural graph"

        allowlist = load_allowlist(default_allowlist_path())
        gating, _allowed, _stale = partition_findings(
            package_lint(), allowlist)
        touching = [f for f in gating if "alloc_slab" in f.path]
        assert touching == [], \
            "columnar contract must lint clean:\n" + \
            "\n".join(f.render() for f in touching)
        assert not any("alloc_slab" in e or "SlabAlloc" in e
                       for e in allowlist), \
            "columnar contract must not need allowlist entries"

    def test_obs_plane_rides_the_gates(self):
        """ISSUE 10 satellite: the trace & telemetry plane — the span
        tracer (obs/trace.py), the unified metrics registry
        (obs/registry.py), the flight recorder + stall watchdog
        (obs/flight.py), and the trace threading through rpc/broker/
        applier/fsm — is inside every gate's scan set, strict-clean,
        with zero allowlist entries of its own."""
        from nomad_tpu.analysis import (default_package_root,
                                        load_allowlist)
        from nomad_tpu.analysis.callgraph import CallGraph

        pkg = default_package_root()
        graph = package_graph()
        for qual in (
            "nomad_tpu.obs.trace:Tracer.record",
            "nomad_tpu.obs.trace:Tracer.snapshot",
            "nomad_tpu.obs.trace:Tracer._append",
            "nomad_tpu.obs.trace:Tracer.chrome_trace",
            "nomad_tpu.obs.registry:MetricsRegistry.register",
            "nomad_tpu.obs.registry:MetricsRegistry.snapshot",
            "nomad_tpu.obs.registry:flatten",
            "nomad_tpu.obs.flight:FlightRecorder.record",
            "nomad_tpu.obs.flight:StallWatchdog._run",
            "nomad_tpu.obs.flight:StallWatchdog.stop",
            "nomad_tpu.server.fsm:NomadFSM._record_apply_spans",
            "nomad_tpu.server.server:Server._setup_obs_registry",
        ):
            assert qual in graph.functions, \
                f"{qual} missing from the interprocedural graph"

        allowlist = load_allowlist(default_allowlist_path())
        gating, _allowed, _stale = partition_findings(
            package_lint(), allowlist)
        touching = [f for f in gating if "nomad_tpu/obs" in f.path
                    or f.path.startswith("obs/") or "/obs/" in f.path]
        assert touching == [], \
            "obs plane must lint clean:\n" + \
            "\n".join(f.render() for f in touching)
        assert not any("obs/" in e or "Tracer" in e or
                       "FlightRecorder" in e or "StallWatchdog" in e
                       for e in allowlist), \
            "obs plane must not need allowlist entries"

    def test_partitioned_verify_rides_the_gates(self):
        """ISSUE 13 satellite: the partitioned window verify — the
        claim-graph partitioner + component walks
        (ops/plan_conflict.py), the component executor + committer
        pipeline + window-batched fence (server/plan_apply.py), the
        deadline-aware plan queue (server/plan_queue.py), and the
        broker's wheel-backed nack timers + targeted wakeups + token
        mirror (server/eval_broker.py) — is inside every gate's scan
        set, strict-clean, with ZERO new allowlist entries (the round
        RETIRED the applier's respond-thread leak waiver)."""
        from nomad_tpu.analysis import (default_package_root,
                                        load_allowlist)
        from nomad_tpu.analysis.callgraph import CallGraph

        pkg = default_package_root()
        graph = package_graph()
        for qual in (
            "nomad_tpu.ops.plan_conflict:partition_window",
            "nomad_tpu.ops.plan_conflict:_walk_component",
            "nomad_tpu.ops.plan_conflict:_evaluate_window_vec",
            "nomad_tpu.ops.plan_conflict:_Frame.__init__",
            "nomad_tpu.ops.plan_conflict:_array_pass",
            "nomad_tpu.server.plan_apply:_Committer._run",
            "nomad_tpu.server.plan_apply:_Committer.stop",
            "nomad_tpu.server.plan_apply:PlanApplier._fence_window",
            "nomad_tpu.server.plan_apply:PlanApplier._commit_job",
            "nomad_tpu.server.plan_queue:PlanQueue.drain_pending",
            "nomad_tpu.server.plan_queue:PlanQueue.await_depth",
            "nomad_tpu.server.eval_broker:EvalBroker.outstanding_many",
            "nomad_tpu.server.eval_broker:EvalBroker._nack_expired",
        ):
            assert qual in graph.functions, \
                f"{qual} missing from the interprocedural graph"

        allowlist = load_allowlist(default_allowlist_path())
        gating, _allowed, _stale = partition_findings(
            package_lint(), allowlist)
        touching = [f for f in gating
                    if "plan_conflict" in f.path
                    or "plan_apply" in f.path
                    or "plan_queue" in f.path
                    or "eval_broker" in f.path]
        assert touching == [], \
            "partitioned-verify paths must lint clean:\n" + \
            "\n".join(f.render() for f in touching)
        assert not any("plan_conflict" in e or "plan_queue" in e
                       or "eval_broker" in e
                       or "_Committer" in e or "plan_apply" in e
                       for e in allowlist), \
            "partitioned verify must not need allowlist entries " \
            "(the respond-thread waiver was retired this round)"
        # The fixed-sleep ratchet stays 0 (asserted by its own test
        # below); the gather wait is a condition, not a sleep.

    def test_control_plane_rides_the_gates(self):
        """ISSUE 14 satellite: the feedback control plane — the railed
        actuator + tick loop (control/controller.py), the knob wiring
        (control/wiring.py), and the actuator seams it grew in the
        runtime (OverloadController.set_ratios, the pipeline's
        in-flight gate, the registry sampler) — is inside every gate's
        scan set (blocking-under-lock, cross-function lock-order, and
        thread/future lifecycle: the tick thread and the metrics
        sampler must be joinable), strict-clean, with ZERO allowlist
        entries of its own; the fixed-sleep ratchet stays 0."""
        from nomad_tpu.analysis import default_package_root
        from nomad_tpu.analysis.callgraph import CallGraph

        pkg = default_package_root()
        graph = package_graph()
        for qual in (
            "nomad_tpu.control.controller:Actuator.apply",
            "nomad_tpu.control.controller:Actuator.pin",
            "nomad_tpu.control.controller:Controller.tick",
            "nomad_tpu.control.controller:Controller._run",
            "nomad_tpu.control.controller:Controller.stop",
            "nomad_tpu.control.controller:Controller.stats",
            "nomad_tpu.control.wiring:server_controller",
            "nomad_tpu.control.wiring:wire_applier",
            "nomad_tpu.control.wiring:wire_overload",
            "nomad_tpu.control.wiring:wire_runner",
            "nomad_tpu.server.overload:OverloadController.set_ratios",
            "nomad_tpu.scheduler.pipeline:"
            "PipelinedEvalRunner._admit_inflight",
            "nomad_tpu.obs.registry:MetricsRegistry.collect",
            "nomad_tpu.obs.registry:MetricsRegistry._sample",
        ):
            assert qual in graph.functions, \
                f"{qual} missing from the interprocedural graph"

        allowlist = load_allowlist(default_allowlist_path())
        gating, _allowed, _stale = partition_findings(
            package_lint(), allowlist)
        touching = [f for f in gating if "control/" in f.path
                    or "nomad_tpu/control" in f.path]
        assert touching == [], \
            "control plane must lint clean:\n" + \
            "\n".join(f.render() for f in touching)
        assert not any("control/" in e or "Actuator" in e
                       or "Controller." in e for e in allowlist), \
            "control plane must not need allowlist entries"
        # The controller tick thread is joinable by construction:
        # a thread-lifecycle finding against it would land in
        # `gating` above — assert the whole rule family stays silent
        # for the new modules.
        assert not any(f.rule.endswith("-leak")
                       and ("control" in f.path
                            or "registry" in f.path)
                       for f in gating)

    def test_changed_mode_covers_devlint(self, tmp_path, capsys):
        """`lint -changed REV` reports device-plane findings in touched
        files and filters pre-existing ones — devlint rides the same
        pre-push loop as every other pass."""
        import subprocess
        import textwrap as _tw

        from nomad_tpu.cli.main import main

        def git(*args):
            subprocess.run(["git", "-C", str(tmp_path), *args],
                           check=True, capture_output=True,
                           env={"GIT_AUTHOR_NAME": "t",
                                "GIT_AUTHOR_EMAIL": "t@t",
                                "GIT_COMMITTER_NAME": "t",
                                "GIT_COMMITTER_EMAIL": "t@t",
                                "HOME": str(tmp_path),
                                "PATH": os.environ.get("PATH", "")})

        bad = _tw.dedent("""
            import jax

            def _impl(x):
                return x

            kern = jax.jit(_impl)
            """)
        bad_caller = _tw.dedent("""
            from pkg.kern import kern

            def _put(x):
                import jax
                return jax.device_put(x)

            def bypass(x):
                return kern(_put(x))
            """)
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "kern.py").write_text(bad)
        (pkg / "untouched.py").write_text(
            bad_caller.replace("def bypass", "def old_bypass"))
        (pkg / "touched.py").write_text("def ok():\n    return 1\n")
        git("init", "-q")
        git("add", "-A")
        git("commit", "-qm", "base")
        (pkg / "touched.py").write_text(bad_caller)
        rc = main(["lint", str(pkg), "-changed", "HEAD",
                   "-allowlist", str(tmp_path / "none.txt")])
        out = capsys.readouterr().out
        assert rc == 1
        assert "touched.py" in out and "mesh-bypass" in out
        assert "untouched.py" not in out, \
            "changed-mode must filter pre-existing devlint findings"

    def test_device_plane_rides_the_gates(self):
        """ISSUE 15 tentpole: the device-plane passes
        (analysis/devlint.py) cover the whole device core — the jit
        kernels (ops/binpack.py, parallel/mesh.py), the dispatch seams
        (scheduler/jax_binpack.py, scheduler/batch.py,
        scheduler/pipeline.py), and the residency plane
        (models/fleet.py, parallel/devices.py) — strict-clean, with
        ZERO allowlist entries of their own and the kernels actually
        discovered (a pass that finds no kernels gates nothing)."""
        from nomad_tpu.analysis import default_package_root
        from nomad_tpu.analysis import devlint
        from nomad_tpu.analysis.callgraph import CallGraph

        pkg = default_package_root()
        graph = package_graph()
        for qual in (
            "nomad_tpu.scheduler.jax_binpack:"
            "JaxBinPackScheduler.dispatch_device",
            "nomad_tpu.scheduler.jax_binpack:"
            "JaxBinPackScheduler._dispatch_device_sharded",
            "nomad_tpu.scheduler.batch:BatchEvalRunner._process",
            "nomad_tpu.models.fleet:UsageMirror.device_usage_sharded",
            "nomad_tpu.models.fleet:UsageMirror._attach_device",
            "nomad_tpu.models.fleet:ShardedResidency.prepare",
            "nomad_tpu.parallel.devices:put_counted",
            "nomad_tpu.parallel.devices:fetch_host",
            "nomad_tpu.parallel.mesh:place_sequence_sharded",
        ):
            assert qual in graph.functions, \
                f"{qual} missing from the interprocedural graph"

        cov: dict = {}
        findings = devlint.analyze_package(pkg, graph=graph,
                                           coverage_out=cov)
        # The pass sees the real kernel family (4 unsharded binpack
        # kernels + the sharded twins + the mirror scatter) and judges
        # every dispatch operand placed.
        assert cov["kernels"] >= 8, cov
        assert cov["kernel_call_sites"] >= 6, cov
        assert cov["host_args"] == 0, cov
        assert cov["placed_args"] > 0 and cov["transfer_sites"] > 0
        assert findings == [], "device plane must lint clean:\n" + \
            "\n".join(f.render() for f in findings)
        allowlist = load_allowlist(default_allowlist_path())
        for rule in ("mesh-bypass", "resident-bypass", "sharding-mix",
                     "transfer-under-lock", "transfer-in-hot-loop",
                     "recompile-churn"):
            assert not any(e.startswith(rule + ":") for e in allowlist), \
                f"device-plane rule {rule} must not need allowlist " \
                "entries (use a justified in-code devlint-ok marker)"

    def test_lever_inventory(self):
        """Every ``NOMAD_TPU_*`` name the package mentions, anywhere:
        the executor policy, the mesh policy, the fault plan and the
        columnar-store switch.  A fifth has to be argued for — each is
        a path every later change pays for twice."""
        import re

        from nomad_tpu.analysis import default_package_root

        names: set = set()
        for root, _dirs, files in os.walk(default_package_root()):
            for name in files:
                if name.endswith(".pyc"):
                    continue
                with open(os.path.join(root, name), "rb") as fh:
                    names.update(
                        m.decode() for m in
                        re.findall(rb"NOMAD_TPU_[A-Z0-9_]+", fh.read()))
        assert names == {"NOMAD_TPU_EXECUTOR", "NOMAD_TPU_MESH",
                         "NOMAD_TPU_FAULTS", "NOMAD_TPU_COLUMNAR"}

    def test_lint_json_reports_devlint_coverage(self, capsys):
        """The device-plane passes' self-coverage rides the same -json
        block as the call graph's (blind spots visible, not silent)."""
        import json as _json

        from nomad_tpu.cli.main import main

        assert main(["lint", "-json"]) == 0
        doc = _json.loads(capsys.readouterr().out)
        dev = doc["coverage"]["devlint"]
        assert set(dev) >= {"kernels", "kernel_call_sites",
                            "placed_args", "host_args",
                            "transfer_sites", "hot_functions",
                            "waived"}
        assert dev["kernels"] > 0 and dev["host_args"] == 0
        # The one deliberate under-lock site (the mirror's bounded
        # scatter maintenance) is marker-waived AND counted.
        assert dev["waived"] >= 1

    def test_consensus_plane_rides_the_gates(self):
        """ISSUE 16 tentpole: the consensus-plane passes
        (analysis/consensuslint.py) cover the replicated core — the FSM
        apply/restore closure, every store commit method, the
        leadership-fenced dispatch sites, and the full RPC endpoint
        table — strict-clean on the real tree, with ZERO allowlist
        entries of their own and the roots actually discovered."""
        from nomad_tpu.analysis import consensuslint, default_package_root
        from nomad_tpu.analysis.callgraph import CallGraph
        from nomad_tpu.server.endpoints import CONSISTENT_READS

        pkg = default_package_root()
        graph = package_graph()
        for qual in (
            "nomad_tpu.server.fsm:NomadFSM.apply",
            "nomad_tpu.server.fsm:NomadFSM.restore",
            "nomad_tpu.state.store:StateStore.upsert_job",
            "nomad_tpu.state.store:StateStore.delete_eval",
            "nomad_tpu.state.store:StateStore.upsert_allocs_batched",
            "nomad_tpu.server.server:Server.node_heartbeat",
            "nomad_tpu.server.server:Server.establish_leadership",
            "nomad_tpu.server.endpoints:Endpoints.job_register",
        ):
            assert qual in graph.functions, \
                f"{qual} missing from the interprocedural graph"

        cov: dict = {}
        findings = consensuslint.analyze_package(pkg, graph=graph,
                                                 coverage_out=cov)
        assert findings == [], "consensus plane must lint clean:\n" + \
            "\n".join(f.render() for f in findings)
        # The determinism pass saw the real apply surface...
        assert cov["apply_roots"] >= 30, cov
        assert cov["apply_closure"] >= cov["apply_roots"]
        # ...the fencing pass saw the real dispatch sites...
        assert cov["fence_targets"] >= 10, cov
        assert cov["fenced_functions"] > 0
        # ...and the contract pass classified the full endpoint table.
        table = cov["endpoint_contract"]
        assert len(table) >= 30, table
        stale_safe = {m for m, c in table.items() if c == "stale-safe"}
        assert stale_safe == set(CONSISTENT_READS), \
            "stale-safe classification must match CONSISTENT_READS " \
            f"exactly: {stale_safe ^ set(CONSISTENT_READS)}"
        assert table["Job.Evaluate"] == "leader-only"
        assert table["Status.Ping"] == "server-local"
        # The three audited sites (timetable witness, broker-fenced
        # enqueue, host-local controller) are waived AND counted.
        assert cov["waived"] >= 3, cov
        allowlist = load_allowlist(default_allowlist_path())
        for rule in ("apply-wall-clock", "apply-rng", "apply-env",
                     "apply-iter-order", "apply-float-accum",
                     "leader-fence", "read-consistency",
                     "stale-read-bypass"):
            assert not any(e.startswith(rule + ":") for e in allowlist), \
                f"consensus rule {rule} must not need allowlist " \
                "entries (use a justified in-code consensus-ok marker)"

    def test_lint_json_reports_consensuslint_coverage(self, capsys):
        """-json schema v3: top-level schema_version plus the consensus
        coverage block carrying the endpoint read-consistency table."""
        import json as _json

        from nomad_tpu.cli.main import main

        assert main(["lint", "-json"]) == 0
        doc = _json.loads(capsys.readouterr().out)
        assert doc["schema_version"] == 3
        cons = doc["coverage"]["consensuslint"]
        assert set(cons) >= {"apply_roots", "apply_closure",
                             "sinks_excluded", "fence_targets",
                             "fenced_functions", "endpoint_contract",
                             "stale_safe_reads", "leader_only_reads",
                             "waived"}
        assert cons["apply_roots"] > 0 and cons["fence_targets"] > 0
        table = cons["endpoint_contract"]
        assert cons["stale_safe_reads"] == \
            sum(1 for c in table.values() if c == "stale-safe")
        assert set(table.values()) <= {"stale-safe", "leader-only",
                                       "local-read", "unfenced-read",
                                       "write", "server-local"}

    def test_changed_mode_covers_consensuslint(self, tmp_path, capsys):
        """`lint -changed REV` reports consensus-plane findings in
        touched files and filters pre-existing ones."""
        import subprocess
        import textwrap as _tw

        from nomad_tpu.cli.main import main

        def git(*args):
            subprocess.run(["git", "-C", str(tmp_path), *args],
                           check=True, capture_output=True,
                           env={"GIT_AUTHOR_NAME": "t",
                                "GIT_AUTHOR_EMAIL": "t@t",
                                "GIT_COMMITTER_NAME": "t",
                                "GIT_COMMITTER_EMAIL": "t@t",
                                "HOME": str(tmp_path),
                                "PATH": os.environ.get("PATH", "")})

        bad = _tw.dedent("""
            import time

            class TinyFSM:
                def apply(self, index, entry):
                    return (entry, time.time())
            """)
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "untouched.py").write_text(
            bad.replace("TinyFSM", "OldFSM"))
        (pkg / "touched.py").write_text("def ok():\n    return 1\n")
        git("init", "-q")
        git("add", "-A")
        git("commit", "-qm", "base")
        (pkg / "touched.py").write_text(bad)
        rc = main(["lint", str(pkg), "-changed", "HEAD",
                   "-allowlist", str(tmp_path / "none.txt")])
        out = capsys.readouterr().out
        assert rc == 1
        assert "touched.py" in out and "apply-wall-clock" in out
        assert "untouched.py" not in out, \
            "changed-mode must filter pre-existing consensus findings"

    def test_failure_plane_rides_the_gates(self):
        """ISSUE 19 tentpole: the failure-plane passes
        (analysis/faultlint.py) cover deadline propagation from every
        serving entry, the full I/O-boundary->fault-site coverage
        table, and retry/shed safety — strict-clean on the real tree
        with every boundary covered or waived and ZERO allowlist
        entries of their own."""
        from nomad_tpu.analysis import default_package_root, faultlint
        from nomad_tpu.analysis.callgraph import CallGraph
        from nomad_tpu.faultinject.plan import SITES

        pkg = default_package_root()
        graph = package_graph()
        # The failure-plane roots the passes hinge on must exist in the
        # interprocedural graph (a rename would silently hollow the
        # gate out).
        for qual in (
            "nomad_tpu.server.endpoints:Endpoints._admitted_body",
            "nomad_tpu.server.endpoints:Endpoints._forward",
            "nomad_tpu.server.overload:restamp_forward",
            "nomad_tpu.server.plan_apply:PlanApplier._wait_commit",
            "nomad_tpu.faultinject:fire",
            "nomad_tpu.faultinject:fire_rpc",
            "nomad_tpu.utils.retry:RetryPolicy.call",
        ):
            assert qual in graph.functions, \
                f"{qual} missing from the interprocedural graph"

        cov: dict = {}
        findings = faultlint.analyze_package(pkg, graph=graph,
                                             coverage_out=cov)
        assert findings == [], "failure plane must lint clean:\n" + \
            "\n".join(f.render() for f in findings)
        # Pass 1 saw the real serving surface: the endpoint table minus
        # the liveness lane, plus the loop entries, and a closure
        # strictly larger than the entry set.
        assert cov["entries"] >= 30, cov
        assert cov["entries_exempt_liveness"] >= 1
        assert cov["entry_closure"] > cov["entries"]
        assert cov["wait_sites"] > 0
        # Pass 2: every registered site is consulted by live code, and
        # EVERY boundary row is covered or carries a reviewed waiver —
        # the 100% covered-or-waived gate.
        assert cov["dead_sites"] == []
        assert set(cov["sites"]) == set(SITES)
        assert all(n > 0 for n in cov["sites"].values()), cov["sites"]
        assert cov["boundary_count"] >= 40, cov["boundary_count"]
        assert cov["covered_fraction"] == 1.0, [
            b for b in cov["boundaries"]
            if b["covered_by"] is None and not b["waived"]]
        # Pass 3 saw the retry closures and the shed raisers, and the
        # committed-state appliers reach none of them unforced.
        assert cov["retry_closures"] >= 1
        assert cov["shed_raisers"] >= 3
        assert cov["retry_tainted"] == 0
        assert cov["apply_shed_calls"] == 0
        # Failure-plane rules never go through the allowlist: waivers
        # live in-code as justified faultlint-ok markers.
        allowlist = load_allowlist(default_allowlist_path())
        for rule in ("unbounded-wait", "deadline-drop",
                     "uninjectable-io", "dead-site", "retry-unsafe"):
            assert not any(e.startswith(rule + ":") for e in allowlist), \
                f"faultlint rule {rule} must not need allowlist " \
                "entries (use a justified in-code faultlint-ok marker)"

    def test_lint_json_reports_faultlint_coverage(self, capsys):
        """-json schema v3 ships the faultlint coverage block with the
        boundary->fault-site table."""
        import json as _json

        from nomad_tpu.cli.main import main

        assert main(["lint", "-json"]) == 0
        doc = _json.loads(capsys.readouterr().out)
        assert doc["schema_version"] == 3
        fl = doc["coverage"]["faultlint"]
        assert set(fl) >= {"entries", "entry_closure", "wait_sites",
                           "unbounded_waits", "transport_drops",
                           "sites", "dead_sites", "boundaries",
                           "boundary_count", "boundaries_covered",
                           "boundaries_waived", "covered_fraction",
                           "retry_closures", "retry_tainted",
                           "shed_raisers", "apply_shed_calls", "waived"}
        assert fl["covered_fraction"] == 1.0
        rows = fl["boundaries"]
        assert len(rows) == fl["boundary_count"] >= 40
        for row in rows:
            assert set(row) == {"function", "path", "line", "kind",
                                "root", "covered_by", "waived"}
            assert row["covered_by"] is not None or row["waived"], row

    def test_changed_mode_covers_faultlint(self, tmp_path, capsys):
        """`lint -changed REV` reports failure-plane findings in touched
        files and filters pre-existing ones; `-sarif` in the same run
        carries the filtered set."""
        import json as _json
        import subprocess
        import textwrap as _tw

        from nomad_tpu.cli.main import main

        def git(*args):
            subprocess.run(["git", "-C", str(tmp_path), *args],
                           check=True, capture_output=True,
                           env={"GIT_AUTHOR_NAME": "t",
                                "GIT_AUTHOR_EMAIL": "t@t",
                                "GIT_COMMITTER_NAME": "t",
                                "GIT_COMMITTER_EMAIL": "t@t",
                                "HOME": str(tmp_path),
                                "PATH": os.environ.get("PATH", "")})

        # The forwarding form of deadline-drop: re-base the envelope,
        # then forward over the pool without clipping the transport
        # wait to it.
        bad = _tw.dedent("""
            def restamp_forward(args, clock):
                return args

            class Fwd:
                def __init__(self, conn_pool):
                    self.conn_pool = conn_pool

                def forward(self, addr, method, args):
                    restamp_forward(args, None)
                    return self.conn_pool.call(addr, method, args)
            """)
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "untouched.py").write_text(bad.replace("Fwd", "OldFwd"))
        (pkg / "touched.py").write_text("def ok():\n    return 1\n")
        git("init", "-q")
        git("add", "-A")
        git("commit", "-qm", "base")
        (pkg / "touched.py").write_text(bad)
        sarif_path = tmp_path / "lint.sarif"
        rc = main(["lint", str(pkg), "-changed", "HEAD",
                   "-sarif", str(sarif_path),
                   "-allowlist", str(tmp_path / "none.txt")])
        out = capsys.readouterr().out
        assert rc == 1
        assert "touched.py" in out and "deadline-drop" in out
        assert "untouched.py" not in out, \
            "changed-mode must filter pre-existing faultlint findings"
        sarif = _json.loads(sarif_path.read_text())
        assert sarif["version"] == "2.1.0"
        run = sarif["runs"][0]
        uris = [r["locations"][0]["physicalLocation"]
                 ["artifactLocation"]["uri"] for r in run["results"]]
        assert any("touched.py" in u for u in uris)
        assert not any("untouched.py" in u for u in uris), \
            "-sarif must carry the -changed-filtered set"

    def test_sarif_log_shape(self, tmp_path, capsys):
        """`lint -sarif PATH` writes a well-formed SARIF 2.1.0 log:
        rule inventory in the driver, one result per finding with
        file/line, and the coverage block under run properties."""
        import json as _json

        from nomad_tpu.cli.main import main

        bad = textwrap.dedent("""
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0
                def inc(self):
                    with self._lock:
                        self.n += 1
                def bad(self):
                    self.n = 0
        """)
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(bad)
        sarif_path = tmp_path / "out.sarif"
        rc = main(["lint", str(pkg), "-sarif", str(sarif_path),
                   "-allowlist", str(tmp_path / "none.txt")])
        capsys.readouterr()
        assert rc == 1
        doc = _json.loads(sarif_path.read_text())
        assert doc["version"] == "2.1.0"
        assert "sarif-schema-2.1.0" in doc["$schema"]
        run = doc["runs"][0]
        driver = run["tool"]["driver"]
        assert driver["name"] == "nomad-tpu-lint"
        rule_ids = {r["id"] for r in driver["rules"]}
        results = run["results"]
        assert results, "the synthetic defect must produce results"
        for r in results:
            assert r["ruleId"] in rule_ids
            loc = r["locations"][0]["physicalLocation"]
            assert loc["artifactLocation"]["uri"].endswith("mod.py")
            assert loc["region"]["startLine"] >= 1
            assert r["level"] in ("error", "note")
        assert "coverage" in run["properties"]

    def test_fixed_sleep_ratchet_is_clean(self):
        """Every fixed time.sleep in the test tree is either converted
        to wait_until or carries a '# sleep-ok: why' justification —
        the blocking classifier's test-tree mode stays quiet."""
        from nomad_tpu.analysis import blocking

        here = os.path.dirname(os.path.abspath(__file__))
        leftovers = blocking.scan_test_sleeps(here)
        assert leftovers == [], "unjustified fixed sleeps:\n" + \
            "\n".join(f.render() for f in leftovers)


# ---------------------------------------------------------------------------
# 2a. lock-discipline analyzer units
# ---------------------------------------------------------------------------

class TestLockcheck:
    def test_bare_write_flagged(self, tmp_path):
        pkg = write_pkg(tmp_path, "p1", """
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0
                def inc(self):
                    with self._lock:
                        self.n += 1
                def bad(self):
                    self.n = 0
        """)
        fs = lockcheck.analyze_package(pkg)
        assert [f.rule for f in fs] == ["bare-write"]
        assert fs[0].where == "C.n"
        assert "bad" in fs[0].message

    def test_locked_suffix_convention_trusted(self, tmp_path):
        pkg = write_pkg(tmp_path, "p2", """
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0
                def inc(self):
                    with self._lock:
                        self._inc_locked()
                def _inc_locked(self):
                    self.n += 1
        """)
        assert lockcheck.analyze_package(pkg) == []

    def test_private_helper_called_under_lock_inferred(self, tmp_path):
        pkg = write_pkg(tmp_path, "p3", """
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0
                def inc(self):
                    with self._lock:
                        self._bump()
                def dec(self):
                    with self._lock:
                        self._bump()
                def _bump(self):
                    self.n += 1
        """)
        assert lockcheck.analyze_package(pkg) == []

    def test_ctor_only_helper_exempt(self, tmp_path):
        pkg = write_pkg(tmp_path, "p4", """
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0
                    self._restore()
                def _restore(self):
                    self.n = 42
                def inc(self):
                    with self._lock:
                        self.n += 1
        """)
        assert lockcheck.analyze_package(pkg) == []

    def test_threadsafe_containers_exempt(self, tmp_path):
        pkg = write_pkg(tmp_path, "p5", """
            import queue
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()
                def locked_put(self, x):
                    with self._lock:
                        self._q.put(x)
                def bare_put(self, x):
                    self._q.put(x)
        """)
        assert lockcheck.analyze_package(pkg) == []

    def test_condition_aliases_its_lock(self, tmp_path):
        pkg = write_pkg(tmp_path, "p6", """
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cond = threading.Condition(self._lock)
                    self.items = []
                def put(self, x):
                    with self._cond:
                        self.items.append(x)
                def drain(self):
                    with self._lock:
                        self.items.clear()
        """)
        assert lockcheck.analyze_package(pkg) == []

    def test_lock_cycle_reported(self, tmp_path):
        pkg = write_pkg(tmp_path, "p7", """
            import threading
            class Inner:
                def __init__(self):
                    self._lock = threading.Lock()
                def poke(self, outer):
                    with self._lock:
                        outer.touch()
            class Outer:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.inner = Inner()
                def go(self):
                    with self._lock:
                        self.inner.poke(self)
                def touch(self):
                    with self._lock:
                        pass
        """)
        fs = lockcheck.analyze_package(pkg)
        cycles = [f for f in fs if f.rule == "lock-cycle"]
        assert cycles and "Inner._lock" in cycles[0].message \
            and "Outer._lock" in cycles[0].message

    def test_nested_self_acquire_of_plain_lock(self, tmp_path):
        pkg = write_pkg(tmp_path, "p8", """
            import threading
            _LOCK = threading.Lock()
            def outer():
                with _LOCK:
                    inner()
            def inner():
                with _LOCK:
                    pass
        """)
        fs = lockcheck.analyze_package(pkg)
        assert any(f.rule == "nested-self-acquire" for f in fs)

    def test_nested_rlock_not_flagged(self, tmp_path):
        pkg = write_pkg(tmp_path, "p9", """
            import threading
            _LOCK = threading.RLock()
            def outer():
                with _LOCK:
                    inner()
            def inner():
                with _LOCK:
                    pass
        """)
        assert lockcheck.analyze_package(pkg) == []

    def test_module_global_discipline(self, tmp_path):
        pkg = write_pkg(tmp_path, "p10", """
            import threading
            _LOCK = threading.Lock()
            _cache = None
            def set_locked(v):
                global _cache
                with _LOCK:
                    _cache = v
            def set_bare(v):
                global _cache
                _cache = v
        """)
        fs = lockcheck.analyze_package(pkg)
        assert any(f.rule == "bare-write" and
                   f.where.endswith("mod._cache") for f in fs)

    def test_conditionally_guarded_global_not_flagged(self, tmp_path):
        """A `with LOCK:` write nested under if/for/try is guarded; the
        walker must not rescan it at the enclosing bare depth
        (code-review regression)."""
        pkg = write_pkg(tmp_path, "p12", """
            import threading
            _LOCK = threading.Lock()
            _cache = None
            def set_maybe(c, v):
                global _cache
                if c:
                    with _LOCK:
                        _cache = v
            def reader():
                with _LOCK:
                    return _cache
        """)
        assert lockcheck.analyze_package(pkg) == []

    def test_thread_body_does_not_inherit_lock(self, tmp_path):
        """A nested def (thread target) started under the lock runs
        WITHOUT it — its writes are bare."""
        pkg = write_pkg(tmp_path, "p11", """
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0
                def inc(self):
                    with self._lock:
                        self.n += 1
                def spawn(self):
                    with self._lock:
                        def body():
                            self.n = 99
                        threading.Thread(target=body).start()
        """)
        fs = lockcheck.analyze_package(pkg)
        assert [f.rule for f in fs] == ["bare-write"]


# ---------------------------------------------------------------------------
# 2b. JAX tracer-safety lint units
# ---------------------------------------------------------------------------

class TestJaxlint:
    def test_impure_branch_concretize(self, tmp_path):
        pkg = write_pkg(tmp_path, "j1", """
            import time
            import jax

            @jax.jit
            def bad(x):
                t = time.time()
                if x > 0:
                    x = x + t
                return float(x)
        """)
        rules = {f.rule for f in jaxlint.analyze_package(pkg)}
        assert rules == {"impure-call", "traced-branch", "concretize"}

    def test_static_args_and_shapes_exempt(self, tmp_path):
        pkg = write_pkg(tmp_path, "j2", """
            from functools import partial
            import jax
            import jax.numpy as jnp

            @partial(jax.jit, static_argnames=("unroll",))
            def ok(x, unroll):
                if unroll > 1:
                    x = x * 2
                if x.shape[0] > 4:
                    x = x[:4]
                for _ in range(3):
                    x = x + 1
                return jnp.sum(x)
        """)
        assert jaxlint.analyze_package(pkg) == []

    def test_wrapper_form_and_static_argnums(self, tmp_path):
        pkg = write_pkg(tmp_path, "j3", """
            import jax

            def _impl(x, n):
                if n > 2:
                    return x
                if x > 0:
                    return -x
                return x

            kernel = jax.jit(_impl, static_argnums=(1,))
        """)
        fs = jaxlint.analyze_package(pkg)
        assert [f.rule for f in fs] == ["traced-branch"]
        assert "if x > 0" in fs[0].message

    def test_callee_walk(self, tmp_path):
        pkg = write_pkg(tmp_path, "j4", """
            import jax

            def helper(y):
                return y.item()

            @jax.jit
            def root(x):
                return helper(x)
        """)
        fs = jaxlint.analyze_package(pkg)
        assert [f.rule for f in fs] == ["concretize"]
        assert "root -> helper" in fs[0].where

    def test_scan_closure_analyzed(self, tmp_path):
        pkg = write_pkg(tmp_path, "j5", """
            import jax
            from jax import lax

            @jax.jit
            def root(xs):
                def step(carry, x):
                    if x > 0:
                        carry = carry + x
                    return carry, x
                return lax.scan(step, 0.0, xs)
        """)
        fs = jaxlint.analyze_package(pkg)
        assert [f.rule for f in fs] == ["traced-branch"]
        assert "root.step" in fs[0].where

    def test_attr_mutation_flagged(self, tmp_path):
        pkg = write_pkg(tmp_path, "j6", """
            import jax

            state = {}

            @jax.jit
            def root(x, obj):
                obj.cache = x
                return x
        """)
        fs = jaxlint.analyze_package(pkg)
        assert [f.rule for f in fs] == ["attr-mutation"]

    def test_colliding_basenames_resolve_by_dotted_path(self, tmp_path):
        """Two modules named helper.py in different subpackages: the
        callee walk must follow the IMPORTED one, not the first basename
        match (code-review regression)."""
        root = tmp_path / "pkg"
        (root / "a").mkdir(parents=True)
        (root / "b").mkdir()
        (root / "__init__.py").write_text("")
        (root / "a" / "__init__.py").write_text("")
        (root / "b" / "__init__.py").write_text("")
        (root / "a" / "helper.py").write_text(textwrap.dedent("""
            def work(y):
                return y  # clean
        """))
        (root / "b" / "helper.py").write_text(textwrap.dedent("""
            def work(y):
                return y.item()  # concretizes
        """))
        (root / "b" / "kern.py").write_text(textwrap.dedent("""
            import jax
            from pkg.b.helper import work

            @jax.jit
            def root_fn(x):
                return work(x)
        """))
        fs = jaxlint.analyze_package(str(root))
        assert [f.rule for f in fs] == ["concretize"]
        assert fs[0].path.endswith("b/helper.py")

    def test_repo_kernels_are_clean(self):
        """The real kernels (ops/, parallel/, models/) carry no tracer
        hazards — this is what keeps the 98.6x headline's parity
        guarantees enforceable per-PR."""
        assert jaxlint.analyze_package("nomad_tpu") == []


# ---------------------------------------------------------------------------
# 3a. lock-order witness
# ---------------------------------------------------------------------------

class TestLockOrderWitness:
    def _mkmod(self, tmp_path, source):
        import importlib.util
        import sys

        p = tmp_path / f"wit_{abs(hash(source)) % 10**8}.py"
        p.write_text(textwrap.dedent(source))
        spec = importlib.util.spec_from_file_location(p.stem, p)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[p.stem] = mod
        spec.loader.exec_module(mod)
        return mod

    def test_cycle_detected(self, tmp_path):
        w = LockOrderWitness(package_prefix=str(tmp_path))
        with w:
            mod = self._mkmod(tmp_path, """
                import threading
                def make():
                    a = threading.Lock()
                    b = threading.Lock()
                    return a, b
                def ab(a, b):
                    with a:
                        with b: pass
                def ba(a, b):
                    with b:
                        with a: pass
            """)
            a, b = mod.make()
            mod.ab(a, b)
            mod.ba(a, b)
        assert len(w.edges) == 2
        with pytest.raises(AssertionError, match="lock-order cycles"):
            w.check()

    def test_consistent_order_passes(self, tmp_path):
        w = LockOrderWitness(package_prefix=str(tmp_path))
        with w:
            mod = self._mkmod(tmp_path, """
                import threading
                def make():
                    a = threading.Lock()
                    b = threading.Lock()
                    return a, b
                def ab(a, b):
                    with a:
                        with b: pass
            """)
            a, b = mod.make()
            for _ in range(3):
                mod.ab(a, b)
        assert len(w.edges) == 1
        w.check()  # no cycle

    def test_foreign_locks_not_wrapped(self, tmp_path):
        w = LockOrderWitness(package_prefix=str(tmp_path / "nowhere"))
        with w:
            lock = threading.Lock()  # created from test code: unwrapped
            assert type(lock).__name__ != "_WrappedLock"
            with lock:
                pass
        assert w.edges == {}

    def test_condition_wait_notify_roundtrip(self, tmp_path):
        """EvalBroker-style Condition(lock) keeps working (and stays
        tracked) through the wrapper, including the wait/notify
        release-save/acquire-restore path."""
        w = LockOrderWitness(package_prefix=str(tmp_path))
        with w:
            mod = self._mkmod(tmp_path, """
                import threading
                class Q:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._cond = threading.Condition(self._lock)
                        self.items = []
                    def put(self, x):
                        with self._lock:
                            self.items.append(x)
                            self._cond.notify_all()
                    def get(self):
                        with self._lock:
                            while not self.items:
                                self._cond.wait(2.0)
                            return self.items.pop()
            """)
            q = mod.Q()
            out = []
            t = threading.Thread(target=lambda: out.append(q.get()))
            t.start()
            time.sleep(0.05)  # sleep-ok: park the getter in cond.wait first
            q.put(42)
            t.join(3)
        assert out == [42]
        w.check()

    def test_real_broker_plan_queue_workload(self):
        """Cross-check the static result on REAL code: a broker +
        plan-queue + state-store workload under the witness observes
        actual acquisition chains and must stay cycle-free."""
        w = LockOrderWitness()  # defaults to the nomad_tpu package
        with w:
            from nomad_tpu import mock
            from nomad_tpu.server.eval_broker import EvalBroker
            from nomad_tpu.server.plan_queue import PlanQueue
            from nomad_tpu.state import StateStore

            broker = EvalBroker(nack_timeout=5, delivery_limit=2)
            broker.set_enabled(True)
            store = StateStore()
            pq = PlanQueue()
            pq.set_enabled(True)

            for i in range(8):
                ev = mock.eval()
                broker.enqueue(ev)
            done = []

            def worker():
                while True:
                    ev, token = broker.dequeue(["service"], timeout=0.5)
                    if ev is None:
                        return
                    store.upsert_evals(100 + len(done), [ev])
                    broker.ack(ev.id, token)
                    done.append(ev.id)

            threads = [threading.Thread(target=worker) for _ in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(10)
            broker.set_enabled(False)
        assert len(done) == 8
        w.check()
        # The run actually observed package locks (the wrap works).
        assert w.sites


# ---------------------------------------------------------------------------
# 3b. recompile sentinel
# ---------------------------------------------------------------------------

class TestRecompileSentinel:
    def test_budget_trips_on_retrace_storm(self):
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda x: x + 1)
        s = RecompileSentinel(budget=3, extra={"demo": f}).install()
        for n in range(2, 8):  # 6 distinct shapes: 6 traces
            f(jnp.ones((n,)))
        with pytest.raises(AssertionError, match="recompile budget"):
            s.check()

    def test_within_budget_passes(self):
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda x: x * 2)
        s = RecompileSentinel(budget=3, extra={"demo": f}).install()
        f(jnp.ones((4,)))
        f(jnp.ones((4,)))  # cache hit, not a trace
        f(jnp.ones((8,)))
        assert s.report()["demo"] == 2
        s.check()

    def test_repo_kernels_are_watchable(self):
        """The registered kernels expose cache introspection on this jax
        version — if this breaks on an upgrade, the sentinel silently
        watching nothing would be worse than failing here."""
        s = RecompileSentinel().install()
        assert s.supported
        assert any(k.startswith("nomad_tpu.ops.binpack")
                   for k in s._baseline)
        assert s.budget == DEFAULT_BUDGET


# ---------------------------------------------------------------------------
# 4. regression tests for the defects the analyzer surfaced (fixed in
#    this PR — each was a real pre-existing bug)
# ---------------------------------------------------------------------------

class TestAnalyzerFoundDefects:
    def test_fast_exiting_first_task_does_not_kill_siblings(
            self, tmp_path, monkeypatch):
        """bare-write AllocRunner.task_runners (run): the runner dict was
        populated one task at a time AFTER each start — a first task
        reporting dead before its sibling was inserted made _aggregate
        see 1/1 dead tasks and mark the whole alloc dead."""
        from nomad_tpu.client import alloc_runner as ar_mod
        from nomad_tpu.client.alloc_runner import AllocRunner
        from nomad_tpu import mock
        from nomad_tpu.structs import Task, Resources

        class InstantDeadTaskRunner:
            """First task dies synchronously inside start()."""

            def __init__(self, ctx, task, state_dir="", on_state=None):
                self.task = task
                self.on_state = on_state
                self.failed = False

            def restore_state(self):
                return False

            def start(self):
                if self.task.name == "fast":
                    self.on_state(self.task.name, "dead", "exited 0")

        monkeypatch.setattr(ar_mod, "TaskRunner", InstantDeadTaskRunner)

        job = mock.job()
        tg = job.task_groups[0]
        tg.tasks = [
            Task(name="fast", driver="exec", resources=Resources(cpu=10)),
            Task(name="slow", driver="exec", resources=Resources(cpu=10)),
        ]
        alloc = mock.alloc()
        alloc.job = job
        alloc.job_id = job.id
        alloc.task_group = tg.name
        alloc.task_resources = {}
        runner = AllocRunner(alloc, str(tmp_path / "alloc"))
        runner.run()
        # Both runners were published before any started; the dead fast
        # task must NOT have aggregated to a dead/failed alloc.
        assert len(runner.task_runners) == 2
        assert runner.alloc.client_status not in ("dead", "failed")

    def test_task_states_snapshot_is_lock_consistent(self, tmp_path,
                                                     monkeypatch):
        """bare-read AllocRunner.task_states (_set_client_status): the
        published alloc's task_states copy is taken under the lock, so a
        status update always carries the state that produced it."""
        from nomad_tpu.client import alloc_runner as ar_mod
        from nomad_tpu.client.alloc_runner import AllocRunner
        from nomad_tpu import mock
        from nomad_tpu.structs import Task, Resources

        class NoopTaskRunner:
            def __init__(self, ctx, task, state_dir="", on_state=None):
                self.task = task
                self.on_state = on_state
                self.failed = False

            def restore_state(self):
                return False

            def start(self):
                pass

        monkeypatch.setattr(ar_mod, "TaskRunner", NoopTaskRunner)
        job = mock.job()
        tg = job.task_groups[0]
        tg.tasks = [Task(name=f"t{i}", driver="exec",
                         resources=Resources(cpu=10)) for i in range(4)]
        alloc = mock.alloc()
        alloc.job = job
        alloc.job_id = job.id
        alloc.task_group = tg.name
        alloc.task_resources = {}
        statuses = []
        runner = AllocRunner(alloc, str(tmp_path / "alloc"),
                             on_status=lambda a: statuses.append(a))
        runner.run()

        # Hammer state updates from 4 "runner threads" concurrently; the
        # unlocked dict(self.task_states) copy used to race the sibling
        # inserts (RuntimeError: dict changed size during iteration).
        def flip(name):
            for i in range(300):
                state = "running" if i % 2 else "pending"
                runner._on_task_state(name, state, "")

        threads = [threading.Thread(target=flip, args=(f"t{i}",))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(15)
        # Every published status carries an internally consistent copy.
        for a in statuses:
            assert isinstance(a.task_states, dict)

    def test_stale_aggregate_cannot_overwrite_newer_status(
            self, tmp_path, monkeypatch):
        """Publication sequencing: a status computed from an older
        task-state snapshot must not land after (and overwrite) a newer
        one when thread scheduling reorders the publishers
        (code-review regression)."""
        from nomad_tpu.client import alloc_runner as ar_mod
        from nomad_tpu.client.alloc_runner import AllocRunner
        from nomad_tpu import mock

        alloc = mock.alloc()
        alloc.task_resources = {}
        runner = AllocRunner(alloc, str(tmp_path / "alloc"))
        # Seq 2 ("dead") publishes first; the late seq-1 ("running")
        # aggregate must be dropped, not win by arriving last.
        runner._set_client_status("dead", "all tasks completed",
                                  {"t": {"state": "dead"}}, seq=2)
        runner._set_client_status("running", "",
                                  {"t": {"state": "running"}}, seq=1)
        assert runner.alloc.client_status == "dead"
        assert runner.alloc.task_states == {"t": {"state": "dead"}}

    def test_concurrent_applies_snapshot_exactly_once(self, tmp_path):
        """bare-read InmemRaft.snapshots/_entries_since_snap
        (_maybe_snapshot): the threshold check ran outside the lock, so
        concurrent appliers could both pass it and double-compact."""
        from nomad_tpu.server.raft import InmemRaft, SnapshotStore

        class CountingStore(SnapshotStore):
            saves = 0

            def save(self, index, blob):
                type(self).saves += 1
                return super().save(index, blob)

        class NullFSM:
            def apply(self, index, entry):
                return None

            def snapshot(self):
                time.sleep(0.01)  # sleep-ok: widen the check-then-act window
                return b"{}"

            def restore(self, blob):
                pass

        store = CountingStore(str(tmp_path / "snaps"))
        raft = InmemRaft(NullFSM(), snapshots=store, snapshot_threshold=8)
        threads = [threading.Thread(
            target=lambda: [raft.apply(b"e") for _ in range(4)])
            for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        # 8 applies, threshold 8: exactly one snapshot.
        assert CountingStore.saves == 1
