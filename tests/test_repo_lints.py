"""Tier-1 gate for the repo's own static checks (ISSUE 3, extended by
ISSUE 10 and ISSUE 18): ``scripts/check_static.py`` (nine AST passes +
fixture self-tests + the generated lock graph) and
``scripts/check_metrics.py`` run inside the test suite, so a regression
in either gates the whole suite — same pattern the reference uses by
running clippy deny-lists in CI next to the unit tests.

ISSUE 10 adds the tooling contracts: the AST runner must stay IMPORT-FREE
of ``lighthouse_tpu``/``jax`` (so it runs in milliseconds with no device
environment — the property that lets it gate every commit), must finish
under a wall-time budget, and ``--update-baseline`` must round-trip
byte-identically.
"""

import ast
import os
import re
import subprocess
import sys
import time

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))

#: Generous CI budget for the whole AST suite (measured: well under 2 s on
#: this 2-core host).  A pass that starts crawling the filesystem or
#: tracing programs has lost the "pure AST" property this asserts.
CHECK_STATIC_BUDGET_S = 30.0


def _run(script: str, *args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", script), *args],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
        timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


class TestCheckStatic:
    def test_tree_is_clean_and_passes_fire(self):
        """Exit 0 == no un-baselined findings AND every pass still fires on
        its seeded-violation fixture (a blind lint also fails)."""
        res = _run("check_static.py")
        assert res.returncode == 0, (
            f"check_static.py failed:\n{res.stdout}\n{res.stderr}"
        )
        assert "OK" in res.stdout
        assert "9 passes" in res.stdout
        assert "lock graph verified" in res.stdout

    def test_fixtures_detected_without_baseline(self):
        """The self-test alone (fixtures only) must detect every seeded
        violation class — proven by the runner's own expectations."""
        res = _run("check_static.py", "--no-self-test")
        assert res.returncode == 0, (
            f"tree scan (no self-test) failed:\n{res.stdout}\n{res.stderr}"
        )

    def test_wall_time_budget(self):
        """The AST suite gates every commit; it must stay cheap."""
        t0 = time.perf_counter()
        res = _run("check_static.py")
        elapsed = time.perf_counter() - t0
        assert res.returncode == 0
        assert elapsed < CHECK_STATIC_BUDGET_S, (
            f"check_static.py took {elapsed:.1f}s (budget "
            f"{CHECK_STATIC_BUDGET_S}s) — a pass stopped being pure AST?"
        )

    def test_import_free_of_runtime_packages(self):
        """The AST passes must never import lighthouse_tpu or jax: an
        import poison hook aborts the run if any pass tries.  This is the
        property that keeps the lint runnable with no device environment
        (and in milliseconds)."""
        poison = (
            "import builtins, runpy, sys\n"
            "real_import = builtins.__import__\n"
            "def guarded(name, *a, **k):\n"
            "    root = name.split('.')[0]\n"
            "    if root in ('lighthouse_tpu', 'jax', 'jaxlib'):\n"
            "        raise ImportError('check_static must stay import-free "
            "of ' + root)\n"
            "    return real_import(name, *a, **k)\n"
            "builtins.__import__ = guarded\n"
            "sys.argv = ['check_static.py']\n"
            "runpy.run_path(%r, run_name='__main__')\n"
            % os.path.join(REPO_ROOT, "scripts", "check_static.py")
        )
        res = subprocess.run(
            [sys.executable, "-c", poison],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
        )
        # run_path propagates check_static's SystemExit(0) as exit code 0;
        # an ImportError from the poison hook would be a traceback instead.
        assert res.returncode == 0, (
            f"check_static.py imported a runtime package:\n{res.stderr}"
        )
        assert "ImportError" not in res.stderr

    def test_update_baseline_roundtrips_byte_identically(self):
        """--update-baseline immediately after --update-baseline must be a
        no-op: deterministic ordering, no churn."""
        path = os.path.join(REPO_ROOT, "scripts", "analysis", "baseline.txt")
        with open(path, "rb") as f:
            committed = f.read()
        try:
            res1 = _run("check_static.py", "--update-baseline")
            assert res1.returncode == 0, res1.stderr
            with open(path, "rb") as f:
                first = f.read()
            assert first == committed, (
                "--update-baseline changed the committed baseline — the "
                "tree has findings the baseline doesn't reflect"
            )
            res2 = _run("check_static.py", "--update-baseline")
            assert res2.returncode == 0, res2.stderr
            with open(path, "rb") as f:
                second = f.read()
            assert second == first
        finally:
            with open(path, "wb") as f:
                f.write(committed)


class TestPassCoverage:
    """ISSUE 10 satellite: the passes cover the modules added since the
    suite landed (PR 3) — a pass whose SCAN_DIRS rot misses new code."""

    def test_device_purity_discovers_kzg_and_pallas(self):
        from analysis import device_purity_pass as dp
        from analysis.common import is_jit_decorator, parse_file

        tree, _, _ = parse_file(
            os.path.join(REPO_ROOT, "lighthouse_tpu/ops/kzg_device.py"))
        jitted = [
            n.name for n in ast.walk(tree)
            if isinstance(n, ast.FunctionDef)
            and any(is_jit_decorator(d) for d in n.decorator_list)
        ]
        assert "_device_kzg_batch" in jitted

        # no Pallas kernel is in the tree today: the pass must still find
        # the kernel a future pl.pallas_call names
        tree = ast.parse(
            "def _fq_mul_kernel(a_ref, o_ref):\n    pass\n"
            "out = pl.pallas_call(_fq_mul_kernel, out_shape=s)(x)\n")
        assert dp._pallas_kernel_names(tree) == {"_fq_mul_kernel"}

    def test_scan_dirs_cover_device_modules(self):
        from analysis import (
            host_sync_pass,
            lock_order_pass,
            recompile_hazard_pass,
            sharding_pass,
        )

        assert "lighthouse_tpu/ops" in recompile_hazard_pass.SCAN_DIRS
        assert "bench.py" in recompile_hazard_pass.SCAN_DIRS
        assert "lighthouse_tpu/device_pipeline.py" in host_sync_pass.SCAN_DIRS
        assert "lighthouse_tpu/device_supervisor.py" in host_sync_pass.SCAN_DIRS
        assert "lighthouse_tpu/ops" in sharding_pass.SCAN_DIRS
        # the PR-7/PR-8 modules stay under lock-order audit
        for mod in ("lighthouse_tpu/device_pipeline.py",
                    "lighthouse_tpu/scenarios.py",
                    "lighthouse_tpu/fork_choice"):
            assert mod in lock_order_pass.SCAN_DIRS
        # ISSUE 18 satellite (SCAN_DIRS rot): the PR 15-17 modules joined
        # the existing passes' scan lists
        assert "lighthouse_tpu/autotune.py" in lock_order_pass.SCAN_DIRS
        assert "lighthouse_tpu/blackbox.py" in lock_order_pass.SCAN_DIRS
        assert "lighthouse_tpu/autotune.py" in host_sync_pass.SCAN_DIRS
        assert "lighthouse_tpu/blackbox.py" in host_sync_pass.SCAN_DIRS

    def test_concurrency_passes_cover_the_concurrent_tree(self):
        """ISSUE 18: the new race / wallclock / process-boundary passes
        scan the modules their contracts name."""
        from analysis import process_boundary_pass, race_pass, wallclock_pass

        for mod in ("lighthouse_tpu/device_supervisor.py",
                    "lighthouse_tpu/device_pipeline.py",
                    "lighthouse_tpu/device_mesh.py",
                    "lighthouse_tpu/blackbox.py",
                    "lighthouse_tpu/autotune.py",
                    "lighthouse_tpu/scheduler",
                    "lighthouse_tpu/scenarios.py",
                    "lighthouse_tpu/network/transport.py"):
            assert mod in race_pass.SCAN_DIRS, mod
        for mod in ("lighthouse_tpu/scenarios.py",
                    "lighthouse_tpu/fault_injection.py",
                    "lighthouse_tpu/network/peer_manager.py",
                    "scripts/analysis/trajectory.py",
                    # ISSUE 20: the virtual-clock module is scanned too —
                    # its WallClock/telemetry_stamp seams are the only
                    # sanctioned wall-clock reads in the control tree
                    "lighthouse_tpu/virtual_clock.py"):
            assert mod in wallclock_pass.SCAN_DIRS, mod
        # ISSUE 20: scenarios.py lost its sanctioned-context entry when the
        # runner moved onto the virtual clock; only the clock module itself
        # may read wall time now
        assert ("lighthouse_tpu/scenarios.py"
                not in wallclock_pass.SANCTIONED_CONTEXTS)
        assert wallclock_pass.SANCTIONED_CONTEXTS[
            "lighthouse_tpu/virtual_clock.py"] == (
                "WallClock", "telemetry_stamp")
        for mod in ("lighthouse_tpu/device_pipeline.py",
                    "lighthouse_tpu/autotune.py",
                    "lighthouse_tpu/http_api",
                    "lighthouse_tpu/scheduler"):
            assert mod in process_boundary_pass.SCAN_DIRS, mod

    def test_telemetry_scope_joins_the_concurrency_passes(self):
        """ISSUE 19: node-scoped telemetry is under race / lock-order /
        host-sync audit (its seeded fixture proves each pass fires on a
        scope-shaped violation — see the SELF_TEST count bumps)."""
        from analysis import host_sync_pass, lock_order_pass, race_pass

        for pass_mod in (race_pass, lock_order_pass, host_sync_pass):
            assert ("lighthouse_tpu/telemetry_scope.py"
                    in pass_mod.SCAN_DIRS), pass_mod.PASS

    def test_baseline_only_shrinks(self):
        """ISSUE 19/20 ratchet: the concurrency-debt baseline is a
        burn-down list.  51 is the count after the virtual-clock refactor
        burned the entire wallclock section (the _pump_until and settle
        deadline loops now read an injected clock) — PRs may shrink this
        bound, never raise it.  New findings get fixed or pragma'd, not
        baselined."""
        path = os.path.join(REPO_ROOT, "scripts", "analysis", "baseline.txt")
        with open(path, "r", encoding="utf-8") as f:
            entries = [ln for ln in f.read().splitlines()
                       if ln.strip() and not ln.startswith("#")]
        assert len(entries) <= 51, (
            f"baseline grew to {len(entries)} entries (ratchet is 51) — "
            "fix or pragma the new finding instead of baselining it"
        )
        # ISSUE 20: the wallclock section ratchets at ZERO — the scenario
        # control path reads virtual time only, and no new wall-clock read
        # may ever be baselined again
        wallclock = [ln for ln in entries if ln.startswith("wallclock|")]
        assert wallclock == [], (
            "wallclock findings re-entered the baseline — the scenario "
            f"control tree must stay on the virtual clock: {wallclock}"
        )

    def test_wallclock_pass_has_zero_findings(self):
        """ISSUE 20 tentpole gate: scenarios.py and simulator.py carry no
        wall-clock reads at all — not sanctioned, not pragma'd away by a
        whole-file waiver, not baselined.  The pass itself returns clean on
        the live tree."""
        from analysis import wallclock_pass

        assert wallclock_pass.run(REPO_ROOT) == []

    def test_lock_order_has_zero_findings(self):
        from analysis import lock_order_pass

        assert lock_order_pass.run(REPO_ROOT) == []

    def test_race_pass_has_zero_findings(self):
        """The real tree is race-clean: the three findings the pass made on
        landing (ResponseCache.misses outside the lock, Hub partition maps)
        were fixed in source, not baselined."""
        from analysis import race_pass

        assert race_pass.run(REPO_ROOT) == []

    def test_committed_lock_graph_matches_computed(self):
        """lighthouse_tpu/lock_graph.py is generated; drift means the
        runtime sanitizer proves a stale graph."""
        from analysis import lock_order_pass

        ns = {}
        path = os.path.join(REPO_ROOT, "lighthouse_tpu", "lock_graph.py")
        with open(path, "r", encoding="utf-8") as f:
            exec(compile(f.read(), path, "exec"), ns)
        assert list(ns["EDGES"]) == lock_order_pass.acquisition_edges(
            REPO_ROOT)


class TestHostSyncClassification:
    """The sanctioned-sync-point registry classifies the real tree: every
    device materialization lives in a supervisor-worker/bench context, and
    the pipeline builder stays sync-free."""

    def test_tree_has_no_hot_path_sync(self):
        from analysis import host_sync_pass

        violations, sanctioned = host_sync_pass.classify(REPO_ROOT)
        assert violations == [], "\n".join(v.render() for v in violations)
        # the classifier itself must not be blind: the supervised device
        # leg of every op (ops/dispatch.py) DOES sync, and the pass must
        # see both of its waits
        by_file = {v.path for v in sanctioned}
        assert by_file == {"lighthouse_tpu/ops/dispatch.py",
                           "lighthouse_tpu/ops/fq.py", "bench.py"}
        seam = [v for v in sanctioned
                if v.path == "lighthouse_tpu/ops/dispatch.py"]
        assert len(seam) == 2 and {v.context for v in seam} == {"execute"}

    def test_pipeline_builder_thread_is_sync_free(self):
        from analysis import host_sync_pass

        _, sanctioned = host_sync_pass.classify(REPO_ROOT)
        assert not any(
            v.path == "lighthouse_tpu/device_pipeline.py" for v in sanctioned
        ), "the pipeline module must not contain sanctioned sync points"


class TestShardingRegistry:
    def test_registry_covers_every_device_entry(self):
        """ops/batch_axes.py stays a parseable literal covering every
        jitted entry point (the sharding pass enforces it; this asserts
        the registry itself from the test side)."""
        from analysis.common import load_batch_axes

        registry = load_batch_axes(REPO_ROOT)
        assert registry, "BATCH_AXES registry missing or unparseable"
        ops = {spec["op"] for spec in registry.values()}
        assert {"bls_verify", "sha256_pairs", "epoch_deltas",
                "kzg_batch"} <= ops
        for key, spec in registry.items():
            assert spec["batch_axis"] == 0, key
            assert isinstance(spec["reduces_over_batch"], bool), key


class TestDispatchSeam:
    """ops/dispatch.py is the one module that knows the mesh, the fault
    points, the compile mirror, the supervisor and the flight record; the
    op modules reach them only through it."""

    SEAM_ONLY = re.compile(
        r"\bdevice_mesh\b|\bfault_injection\.(?:check|fire)\b"
        r"|\bnote_dispatch\b|\brecord_batch\b"
        r"|\bdevice_supervisor\.run\b|\b_sharded_entry\b")

    def test_only_the_seam_dispatches(self):
        ops_dir = os.path.join(REPO_ROOT, "lighthouse_tpu", "ops")
        hits = []
        for name in sorted(os.listdir(ops_dir)):
            if not name.endswith(".py") or name == "dispatch.py":
                continue
            with open(os.path.join(ops_dir, name), encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if self.SEAM_ONLY.search(line):
                        hits.append(f"ops/{name}:{lineno}: {line.strip()}")
        assert hits == [], "dispatch outside ops/dispatch.py:\n" + "\n".join(hits)


class TestCheckMetrics:
    def test_metrics_registry_lint(self):
        res = _run("check_metrics.py")
        assert res.returncode == 0, (
            f"check_metrics.py failed:\n{res.stdout}\n{res.stderr}"
        )
        assert "OK" in res.stdout


class TestCheckAll:
    """ISSUE 18 satellite: the consolidated gate — check_static,
    check_metrics and the trajectory sentinel in ONE interpreter with a
    single jax-import poison installed before any checker loads."""

    def test_consolidated_gate_passes(self):
        res = _run("check_all.py")
        assert res.returncode == 0, (
            f"check_all.py failed:\n{res.stdout}\n{res.stderr}"
        )
        # every constituent checker reported, through one process
        assert "check_static: OK" in res.stdout
        assert "9 passes" in res.stdout
        assert "check_metrics: OK" in res.stdout
        assert '"trajectory": "ok"' in res.stdout
        assert "check_all: OK (3 checkers" in res.stdout

    def test_constituent_failure_propagates(self, tmp_path):
        """A failing constituent must fail the whole gate: run one checker
        through check_all's own dispatch against an empty artifacts dir
        (the sentinel has nothing to check -> nonzero) and confirm the
        nonzero code surfaces instead of being swallowed."""
        import check_all as ca

        rc = ca._run_checker("trajectory", "analysis.trajectory",
                             ("--check", "--artifacts-dir", str(tmp_path)))
        assert rc != 0


class TestTrajectorySentinel:
    """ISSUE 17: the perf-trajectory sentinel (scripts/analysis/
    trajectory.py) gates the committed round artifacts against the
    committed ribbons, stays import-free of runtime packages (the campaign
    parent invokes it and must never import jax), round-trips its baseline
    byte-identically, and still SEES a seeded regression."""

    BASELINE = os.path.join(
        REPO_ROOT, "scripts", "analysis", "trajectory_baseline.json")

    def test_committed_artifacts_pass_the_committed_ribbons(self):
        res = _run(os.path.join("analysis", "trajectory.py"),
                   "--check", "--strict")
        assert res.returncode == 0, (
            f"trajectory.py failed on the committed artifacts:\n"
            f"{res.stdout}\n{res.stderr}"
        )
        assert '"trajectory": "ok"' in res.stdout

    def test_import_free_of_runtime_packages(self):
        """The sentinel runs from the campaign parent — the process that
        must never import jax — and from bare CI boxes.  An import poison
        proves it stays stdlib-only."""
        poison = (
            "import builtins, runpy, sys\n"
            "real_import = builtins.__import__\n"
            "def guarded(name, *a, **k):\n"
            "    root = name.split('.')[0]\n"
            "    if root in ('lighthouse_tpu', 'jax', 'jaxlib', 'numpy'):\n"
            "        raise ImportError('trajectory must stay import-free "
            "of ' + root)\n"
            "    return real_import(name, *a, **k)\n"
            "builtins.__import__ = guarded\n"
            "sys.argv = ['trajectory.py', '--check']\n"
            "runpy.run_path(%r, run_name='__main__')\n"
            % os.path.join(REPO_ROOT, "scripts", "analysis", "trajectory.py")
        )
        res = subprocess.run(
            [sys.executable, "-c", poison],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
        )
        assert res.returncode == 0, (
            f"trajectory.py imported a runtime package:\n{res.stderr}"
        )
        assert "ImportError" not in res.stderr

    def test_update_baseline_roundtrips_byte_identically(self):
        with open(self.BASELINE, "rb") as f:
            committed = f.read()
        try:
            res1 = _run(os.path.join("analysis", "trajectory.py"),
                        "--update-baseline")
            assert res1.returncode == 0, res1.stderr
            with open(self.BASELINE, "rb") as f:
                first = f.read()
            assert first == committed, (
                "--update-baseline changed the committed trajectory "
                "baseline — an artifact's series drifted without review"
            )
            res2 = _run(os.path.join("analysis", "trajectory.py"),
                        "--update-baseline")
            assert res2.returncode == 0, res2.stderr
            with open(self.BASELINE, "rb") as f:
                second = f.read()
            assert second == first
        finally:
            with open(self.BASELINE, "wb") as f:
                f.write(committed)

    def test_seeded_regression_fails_the_check(self, tmp_path):
        """A 20% drop in a committed series must redden the sentinel (the
        ribbon is ±10%) — proven against the REAL baseline, not a synthetic
        one, so a decoupled extractor cannot pass silently."""
        import json as _json
        import shutil

        src = os.path.join(REPO_ROOT, "BENCH_r07.json")
        dst = tmp_path / "BENCH_r07.json"
        shutil.copy(src, dst)
        doc = _json.loads(dst.read_text())
        doc["serve"]["p99_speedup_min"] *= 0.8
        dst.write_text(_json.dumps(doc))
        res = _run(os.path.join("analysis", "trajectory.py"),
                   "--check", "--artifacts-dir", str(tmp_path))
        assert res.returncode == 1, res.stdout + res.stderr
        assert "serve.p99_speedup_min|cpu" in res.stderr
        assert "fell below the ribbon floor" in res.stderr


class TestBlackboxImportFree:
    def test_blackbox_runs_without_jax(self, tmp_path):
        """The incident journal must stay importable AND functional with
        jax banned: the campaign parent (which must never import jax)
        journals phase lifecycle through it and freezes bundles on phase
        death.  Emit, capture, and the snapshot gather all run under the
        poison — a bundle with error-stubbed sections would mean a seam
        module grew a top-level jax import."""
        probe = (
            "import builtins, json, sys\n"
            "real_import = builtins.__import__\n"
            "def guarded(name, *a, **k):\n"
            "    if name.split('.')[0] in ('jax', 'jaxlib'):\n"
            "        raise ImportError('blackbox must stay jax-free')\n"
            "    return real_import(name, *a, **k)\n"
            "builtins.__import__ = guarded\n"
            "from lighthouse_tpu import blackbox\n"
            "blackbox.configure(directory=%r, retain_bundles=4)\n"
            "blackbox.emit('test', 'poison_probe', op='bls_verify')\n"
            "cap = blackbox.capture('lint_probe')\n"
            "bundle = blackbox.load_bundle("
            "    cap['path'].rsplit('/', 1)[-1])\n"
            "assert bundle['journal'], 'journal window empty'\n"
            "for section in ('supervisor', 'mesh', 'pipeline',\n"
            "                'autotune', 'telemetry'):\n"
            "    snap = bundle['snapshots'][section]\n"
            "    assert 'error' not in (snap or {}), (section, snap)\n"
            "print('BLACKBOX_POISON_OK')\n"
        ) % str(tmp_path / "bundles")
        res = subprocess.run(
            [sys.executable, "-c", probe],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert res.returncode == 0, res.stderr
        assert "BLACKBOX_POISON_OK" in res.stdout
