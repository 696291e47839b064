"""Import-hygiene regression tests.

The package (and the telemetry subsystem, which grows most often) must
stay importable without dragging jax/flax in: the TTFT bench bills every
worker's import chain to ``proc_startup_imports``, and the `trace` CLI is
meant to run on machines that only hold the log files.

The module lists here are NOT hand-maintained: they derive from
``accelerate_tpu.analysis.hygiene`` — the same declared sets
``accelerate-tpu audit`` statically enforces — so the test and the audit
can never drift (adding a host module to the contract is one edit in
hygiene.py). The functional smoke tests below exercise representative
jax-free APIs end to end on top of the derived import sweep.
"""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _probe(statements: str) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", statements],
        capture_output=True, text=True, env=env, timeout=120, cwd=REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr


def _declared():
    # importing the hygiene module itself is jax-free by contract (it is
    # a member of its own declared set — asserted below)
    from accelerate_tpu.analysis import hygiene

    return hygiene


class TestDeclaredModuleSets:
    def test_declared_jax_free_modules_import_light(self):
        """EVERY module in the declared jax-free set imports, in one
        process, without jax/flax/optax appearing in sys.modules — the
        single probe the old per-subsystem list tests collapsed into."""
        hygiene = _declared()
        imports = "\n".join(f"import {m}" for m in hygiene.JAX_FREE_MODULES)
        heavy = ", ".join(repr(m) for m in hygiene.HEAVY_MODULES)
        _probe(
            "import sys\n"
            f"{imports}\n"
            f"heavy = {{m for m in ({heavy}) if m in sys.modules}}\n"
            "assert not heavy, f'declared jax-free set pulled {heavy}'\n"
            "bad = sorted(m for m in sys.modules if 'pallas' in m)\n"
            "assert not bad, f'declared jax-free set pulled pallas: {bad}'"
        )

    def test_declared_pallas_free_modules_import_without_pallas(self):
        """The decode-kernel surfaces (ops + the serving engine) may pull
        jax but must defer pallas to first trace (the _LazyModule
        contract): pallas costs ~0.2 s at import — billed to every
        worker's proc_startup_imports."""
        hygiene = _declared()
        imports = "\n".join(f"import {m}" for m in hygiene.PALLAS_FREE_MODULES)
        _probe(
            "import sys\n"
            f"{imports}\n"
            "bad = sorted(m for m in sys.modules if 'pallas' in m)\n"
            "assert not bad, f'pallas-free set pulled pallas: {bad}'"
        )

    def test_static_hygiene_check_agrees(self):
        """The AST-reachability check `accelerate-tpu audit` runs must be
        clean on the tree whenever the subprocess probes are — if this
        fails while the probes pass, a lazy-import pattern confused the
        static walk and hygiene.py needs teaching, not silencing."""
        from accelerate_tpu.analysis.hygiene import hygiene_findings

        findings = hygiene_findings()
        assert findings == [], [f.to_dict() for f in findings]

    def test_every_declared_module_resolves(self):
        """A rename that silently drops a module from the contract is
        drift — the sets must track real files."""
        hygiene = _declared()
        for name in hygiene.JAX_FREE_MODULES + hygiene.PALLAS_FREE_MODULES:
            assert hygiene.module_file(name, hygiene.repo_root()), name


class TestNoEagerHeavyImports:
    def test_host_lint_pass_stays_light_and_fast(self):
        """The audit host-lint path is the CI gate on log-only machines:
        no jax/flax at import OR during a full lint+hygiene pass, and the
        whole pass stays under 5 seconds."""
        t0 = time.time()
        _probe(
            "import sys, time\n"
            "t0 = time.time()\n"
            "from accelerate_tpu.analysis import host_lint, hygiene\n"
            "fs = host_lint.lint_paths() + hygiene.hygiene_findings()\n"
            "heavy = {m for m in ('jax', 'flax') if m in sys.modules}\n"
            "assert not heavy, f'host lint pulled {heavy}'\n"
            "assert time.time() - t0 < 5.0, f'host lint too slow: {time.time() - t0:.1f}s'\n"
        )
        assert time.time() - t0 < 30.0  # interpreter startup included

    def test_paged_kv_bookkeeping_stays_light(self):
        """The paged-arena host layer (free list, refcounts, prefix-cache
        hashing) is what a router/scheduler tier imports to
        reason about page budgets — numpy-only, never jax/flax."""
        _probe(
            "import sys\n"
            "import accelerate_tpu.serving.pages as pages\n"
            "alloc = pages.PageAllocator(8)\n"
            "cache = pages.PrefixCache(alloc, page_size=4)\n"
            "# the quantized-arena capacity helpers are part of the same\n"
            "# jax-free contract: a router sizes int8/int4 KV budgets with\n"
            "# these on accelerator-less machines\n"
            "assert pages.kv_cache_bits('int8') == 8\n"
            "assert pages.kv_payload_width(64, 'int4') == 32\n"
            "assert pages.kv_token_bytes(2, 64, 'int8', num_layers=4) > 0\n"
            "heavy = {m for m in ('jax', 'flax') if m in sys.modules}\n"
            "assert not heavy, f'serving.pages import pulled {heavy}'"
        )

    def test_scheduler_policy_tier_stays_light(self):
        """The multi-tenant scheduler (WFQ, quotas, admission control,
        the ITL-budget controller) and the fault-injection harness are
        pure host policy — a router tier runs the same admission/shed
        math on machines with no accelerator stack."""
        _probe(
            "import sys\n"
            "import accelerate_tpu.serving.scheduler as sched\n"
            "import accelerate_tpu.serving.faults as faults\n"
            "s = sched.MultiTenantScheduler(sched.SchedulerConfig())\n"
            "sched.PrefillBudgetController(25.0)\n"
            "faults.FaultInjector(seed=0).delay_decode(every=4)\n"
            "heavy = {m for m in ('jax', 'flax') if m in sys.modules}\n"
            "assert not heavy, f'scheduler/faults import pulled {heavy}'"
        )

    def test_ops_plane_stays_light(self):
        """The continuous ops plane — timeline ring, alert rules, usage
        accounting — is host bookkeeping a router/monitoring tier imports
        with no accelerator stack; stricter than the sweep above, only
        numpy may load."""
        _probe(
            "import sys\n"
            "import accelerate_tpu.telemetry.timeline as tlm\n"
            "import accelerate_tpu.telemetry.alerts as alerts\n"
            "import accelerate_tpu.telemetry.usage as usage\n"
            "tl = tlm.Timeline()\n"
            "tl.add_sample({'x': 1.0}, now=1.0)\n"
            "rules = alerts.default_ruleset(itl_slo_ms=25.0)\n"
            "alerts.AlertManager(tl, rules).evaluate(now=1.0)\n"
            "usage.UsageAccountant().note_decode('t')\n"
            "heavy = {m for m in ('jax', 'flax', 'numpy') if m in sys.modules}\n"
            "assert heavy <= {'numpy'}, f'ops-plane import pulled {heavy}'\n"
            "assert 'jax' not in sys.modules and 'flax' not in sys.modules"
        )

    def test_watch_cli_module_stays_light(self):
        """`accelerate-tpu watch` runs from any shell that can reach the
        scrape endpoint or the artifact dir — stdlib only, no jax."""
        _probe(
            "import sys\n"
            "import accelerate_tpu.commands.watch as watch\n"
            "watch.sparkline([1.0, 2.0, 3.0], width=8)\n"
            "watch.parse_prometheus('att_x 1.0\\n')\n"
            "assert 'jax' not in sys.modules, 'watch CLI pulled jax'"
        )

    def test_fleet_plane_stays_light(self):
        """The fleet observability plane (collector, health state
        machine, merge policies, placement view) and the `watch --fleet`
        rendering path run on a router tier with no accelerator stack —
        no jax, no flax, no pallas, end to end through a poll."""
        _probe(
            "import sys\n"
            "import accelerate_tpu.telemetry.fleet as fleet\n"
            "import accelerate_tpu.commands.watch as watch\n"
            "snap = fleet.parse_exposition(\n"
            "    'att_serving_queue_depth 2\\natt_bad NaN\\ntorn line here')\n"
            "assert snap.gauges['serving_queue_depth'] == 2\n"
            "assert fleet.load_score(queue_depth=4, num_slots=4) == 1.0\n"
            "c = fleet.FleetCollector(\n"
            "    [('A', 'http://a/metrics')], clock=lambda: 1.0,\n"
            "    fetch_fn=lambda t: 'att_serving_load_score 0.5\\n'\n"
            "                       'att_serving_queue_depth 1\\n')\n"
            "c.poll_once(now=1.0)\n"
            "view = c.placement_view()\n"
            "assert view and view[0]['load_score'] == 0.5\n"
            "watch.render_fleet_frame(c, ['serving/queue_depth'])\n"
            "heavy = {m for m in ('jax', 'flax') if m in sys.modules}\n"
            "assert not heavy, f'fleet plane import pulled {heavy}'\n"
            "bad = sorted(m for m in sys.modules if 'pallas' in m)\n"
            "assert not bad, f'fleet plane pulled pallas: {bad}'"
        )

    def test_audit_cli_host_pass_stays_light(self):
        """`accelerate-tpu audit --host-only` is the log-only-machine CI
        gate: the whole CLI round trip — parse, lint, hygiene, render —
        must never import jax."""
        _probe(
            "import sys\n"
            "from accelerate_tpu.commands.accelerate_cli import main\n"
            "rc = main(['audit', '--host-only'])\n"
            "assert rc == 0, f'audit --host-only failed: {rc}'\n"
            "heavy = {m for m in ('jax', 'flax') if m in sys.modules}\n"
            "assert not heavy, f'audit --host-only pulled {heavy}'"
        )
