"""Static program auditor + host linter (accelerate_tpu/analysis/).

Contracts of record:
- the host linter flags each seeded bug class in the golden corpus
  (tests/audit_fixtures/bad_host.py) with an EXACT fingerprint and
  severity — fingerprints are stable across line edits, so the golden
  hexes below only change when a check's semantics change;
- the program auditor detects all five seeded violation classes (baked
  constant, donation miss, f32 drift, host callback, weak shape) on
  deliberately-bad jitted programs, again with exact fingerprints;
- the repo's OWN programs and host modules are clean: zero findings over
  the serving engine's full warmup program set (paged, flat and
  donation-on), zero host-lint findings over the tree, and the
  `accelerate-tpu audit` gate exits 0 modulo the checked-in baseline —
  this tier-1 test IS the CI gate;
- `audit` exits non-zero on unbaselined P1 findings; baselined findings
  render their justification; `report` gains an audit section and
  `report --diff --fail` trips on a NEW P1 fingerprint.
"""

import json
import os
import time

import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.analysis import findings as fmod
from accelerate_tpu.analysis import host_lint, hygiene
from accelerate_tpu.analysis import program_audit as pa
from accelerate_tpu.analysis.findings import Baseline, Finding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "audit_fixtures", "bad_host.py")


class TestFindingsModel:
    def test_fingerprint_excludes_volatile_detail(self):
        a = Finding(check="c", severity="P1", target="t.py", anchor="x",
                    message="m", detail={"line": 10})
        b = Finding(check="c", severity="P1", target="t.py", anchor="x",
                    message="different text", detail={"line": 99})
        assert a.fingerprint == b.fingerprint
        assert a.fingerprint != Finding(
            check="c", severity="P1", target="t.py", anchor="y", message="m"
        ).fingerprint

    def test_severity_validated(self):
        with pytest.raises(ValueError):
            Finding(check="c", severity="P9", target="t", message="m")

    def test_sort_and_summarize(self):
        fs = [Finding(check="c", severity=s, target=t, message="m")
              for s, t in (("P3", "b"), ("P1", "z"), ("P2", "a"), ("P1", "a"))]
        ordered = fmod.sort_findings(fs)
        assert [f.severity for f in ordered] == ["P1", "P1", "P2", "P3"]
        assert [f.target for f in ordered[:2]] == ["a", "z"]
        s = fmod.summarize(fs)
        assert (s["findings_total"], s["findings_p1"], s["findings_p2"],
                s["findings_p3"]) == (4, 2, 1, 1)

    def test_baseline_roundtrip_split_and_stale(self, tmp_path):
        f1 = Finding(check="c", severity="P1", target="t", message="m", anchor="1")
        f2 = Finding(check="c", severity="P1", target="t", message="m", anchor="2")
        base = Baseline()
        base.add(f1, "deliberate: tested elsewhere")
        path = str(tmp_path / "base.json")
        base.save(path)
        loaded = Baseline.load(path)
        active, suppressed = loaded.split([f1, f2])
        assert [f.anchor for f in active] == ["2"]
        assert suppressed[0].justification == "deliberate: tested elsewhere"
        # f1 fixed -> its entry is stale
        assert list(loaded.stale_entries([f2])) == [f1.fingerprint]
        assert loaded.stale_entries([f1, f2]) == {}

    def test_baseline_requires_justification(self, tmp_path):
        f1 = Finding(check="c", severity="P1", target="t", message="m")
        with pytest.raises(ValueError):
            Baseline().add(f1, "")
        path = tmp_path / "bad.json"
        path.write_text(json.dumps(
            {"entries": {f1.fingerprint: {"check": "c"}}}))
        with pytest.raises(ValueError):
            Baseline.load(str(path))

    def test_missing_baseline_is_empty(self, tmp_path):
        b = Baseline.load(str(tmp_path / "nope.json"))
        assert b.entries == {}


# the golden corpus: fingerprint -> (check, severity). These hexes are
# the stability contract — they survive line-number edits to the corpus
# and change ONLY when a check's identity semantics change.
GOLDEN_HOST = {
    "fdec54fe0c1d21f1": ("lock-inversion", "P1"),
    "f3c399c337afb176": ("callback-under-lock", "P1"),
    "8a900e8c170b3af0": ("callback-under-lock", "P1"),   # one call level down
    "aaf3ba7d1bd5bc58": ("env-dead-fallback", "P1"),     # the PR 10 shape
    "7c3745f81f7ed85f": ("env-truthy-default", "P1"),
    "729fc4f3939a3ff5": ("env-default-type", "P2"),
    "83a29d1a204a7b0f": ("env-truthy-test", "P2"),
}


class TestHostLintCorpus:
    def test_corpus_findings_exact(self):
        got = {
            f.fingerprint: (f.check, f.severity)
            for f in host_lint.lint_file(FIXTURE, "audit_fixtures/bad_host.py")
        }
        assert got == GOLDEN_HOST

    def test_fingerprints_survive_line_shifts(self):
        with open(FIXTURE) as fh:
            src = fh.read()
        shifted = "# shim\n# shim\n\n" + src
        got = {f.fingerprint for f in
               host_lint.lint_source(shifted, "audit_fixtures/bad_host.py")}
        assert got == set(GOLDEN_HOST)

    def test_lock_inversion_names_both_witnesses(self):
        fs = host_lint.lint_file(FIXTURE, "audit_fixtures/bad_host.py")
        inv = [f for f in fs if f.check == "lock-inversion"]
        assert len(inv) == 1
        assert "BadLockOrder.evaluate" in inv[0].detail["lock_order"]
        assert "BadLockOrder.dump" in inv[0].detail["lock_order"]

    def test_correct_idioms_not_flagged(self):
        src = (
            "import os, threading\n"
            "class Good:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.on_x = None\n"
            "    def run(self):\n"
            "        with self._lock:\n"
            "            todo = [1]\n"
            "        self.on_x()  # AFTER release — the fixed PR 9 shape\n"
            "def workers():\n"
            "    # int-before-fallback: the correct PR 10 fix\n"
            "    n = int(os.environ.get('X_THREADS') or 0)\n"
            "    return max(1, n or 4)\n"
            "def flag():\n"
            "    return os.environ.get('X_FLAG', '0').lower() not in ('0', 'false', '')\n"
            "def name():\n"
            "    return os.environ.get('X_NAME') or None\n"
        )
        assert host_lint.lint_source(src, "good.py") == []

    def test_unbounded_artifact_append_flagged(self):
        src = (
            "import os, json\n"
            "def log_direct(rec):\n"
            "    with open('events.jsonl', 'a') as fh:\n"
            "        fh.write(json.dumps(rec) + '\\n')\n"
            "def log_joined(d, rec):\n"
            "    with open(os.path.join(d, 'alerts-host0.jsonl'), mode='at') as fh:\n"
            "        fh.write(json.dumps(rec) + '\\n')\n"
        )
        fs = host_lint.lint_source(src, "telemetry/whatever.py")
        appends = [f for f in fs if f.check == "artifact-append"]
        assert len(appends) == 2
        assert all(f.severity == "P2" for f in appends)
        assert "ArtifactWriter" in appends[0].message

    def test_artifact_append_exempts_the_writer_and_bounded_io(self):
        src = (
            "def read(path):\n"
            "    with open('events.jsonl') as fh:\n"       # read, not append
            "        return fh.read()\n"
            "def log_txt(rec):\n"
            "    with open('notes.txt', 'a') as fh:\n"      # not a JSONL family
            "        fh.write(rec)\n"
        )
        assert [f for f in host_lint.lint_source(src, "x.py")
                if f.check == "artifact-append"] == []
        # the one place append-mode JSONL opens are the implementation:
        writer_src = "fh = open(path + '.jsonl', 'ab', buffering=0)\n"
        assert host_lint.lint_source(
            writer_src, "accelerate_tpu/telemetry/artifacts.py") == []
        hit = host_lint.lint_source(writer_src, "elsewhere.py")
        assert [f.check for f in hit] == ["artifact-append"]

    def test_repo_host_tree_is_clean(self):
        fs = host_lint.lint_paths()
        assert fs == [], [f.to_dict() for f in fs]

    def test_host_lint_pass_under_5s(self):
        t0 = time.time()
        host_lint.lint_paths()
        hygiene.hygiene_findings()
        assert time.time() - t0 < 5.0


GOLDEN_PROGRAMS = {
    "5e3a99320f932a80": ("baked-constant", "P1"),
    "377ee0ad53732b18": ("donation-miss", "P1"),
    "5242737354c2858c": ("f32-drift", "P1"),
    # anchored on the primitive name: jax 0.9.0 traces jax.debug.print as
    # "debug_print"
    "419353bb8ce4e206": ("host-callback", "P1"),
    "78eceb3181fc6b34": ("weak-shape", "P2"),
}


class TestProgramAuditCorpus:
    def _golden(self, findings, fp):
        assert len(findings) == 1, [f.to_dict() for f in findings]
        f = findings[0]
        assert (f.fingerprint, (f.check, f.severity)) == (fp, GOLDEN_PROGRAMS[fp])
        return f

    def test_baked_constant(self):
        big = jnp.ones((512, 1024), jnp.float32)  # 2 MiB closed over

        def baked(x):
            return x @ big

        f = self._golden(
            pa.audit_program(dict(name="bad_baked", fn=jax.jit(baked),
                                  args=(jnp.ones((8, 512)),))),
            "5e3a99320f932a80",
        )
        assert f.detail["bytes"] == 512 * 1024 * 4

    def test_donation_miss(self):
        def upd(a, b):
            return a + 1.0, b * 2.0

        f = self._golden(
            pa.audit_program(dict(
                name="bad_donate", fn=jax.jit(upd, donate_argnums=(0,)),
                args=(jnp.ones((256, 256)), jnp.ones((256, 256))),
                donate=(0,),
            )),
            "377ee0ad53732b18",
        )
        assert f.detail["arg"] == 1

    def test_donation_skipped_when_deliberately_off(self):
        def upd(a, b):
            return a + 1.0, b * 2.0

        fs = pa.audit_program(dict(
            name="bad_donate", fn=jax.jit(upd),
            args=(jnp.ones((256, 256)), jnp.ones((256, 256))),
            donate=(), donate_expected=False,
        ))
        assert fs == []

    def test_donation_threshold_filters_bookkeeping(self):
        def upd(a, b):
            return a + 1.0, b * 2.0

        fs = pa.audit_program(dict(
            name="small_donate", fn=jax.jit(upd, donate_argnums=(0,)),
            args=(jnp.ones((8, 8)), jnp.ones((8, 8))), donate=(0,),
        ))
        assert fs == []

    def test_f32_drift(self):
        def drift(x, w):
            return x.astype(jnp.float32) @ w.astype(jnp.float32)

        self._golden(
            pa.audit_program(dict(
                name="bad_f32", fn=jax.jit(drift),
                args=(jnp.ones((8, 16), jnp.bfloat16),
                      jnp.ones((16, 16), jnp.bfloat16)),
            )),
            "5242737354c2858c",
        )

    def test_f32_accumulation_not_flagged(self):
        def legit(x, w):
            # bf16 operands, f32 accumulation: the CORRECT recipe
            return jax.lax.dot_general(
                x, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        fs = pa.audit_program(dict(
            name="ok_f32acc", fn=jax.jit(legit),
            args=(jnp.ones((8, 16), jnp.bfloat16),
                  jnp.ones((16, 16), jnp.bfloat16)),
        ))
        assert fs == []

    def test_host_callback(self):
        def cb(x):
            jax.debug.print("x={x}", x=x.sum())
            return x * 2

        self._golden(
            pa.audit_program(dict(name="bad_cb", fn=jax.jit(cb),
                                  args=(jnp.ones((4,)),))),
            "419353bb8ce4e206",
        )

    def test_weak_shape(self):
        def weak(x):
            return x * x.shape[0]  # python int baked from a per-call shape

        self._golden(
            pa.audit_program(dict(
                name="bad_weak", fn=jax.jit(weak),
                args=(jnp.ones((8, 4)),),
                shape_probe=(jnp.ones((16, 4)),),
            )),
            "78eceb3181fc6b34",
        )

    def test_shape_independent_program_passes_probe(self):
        def fine(x):
            return (x * 2.0).sum(axis=-1)

        fs = pa.audit_program(dict(
            name="ok_weak", fn=jax.jit(fine), args=(jnp.ones((8, 4)),),
            shape_probe=(jnp.ones((16, 4)),),
        ))
        assert fs == []

    def test_registry_coverage_cross_check(self):
        def fine(x):
            return x + 1.0

        fs = pa.audit_entrypoints(
            [dict(name="decode_step", fn=jax.jit(fine), args=(jnp.ones((4,)),))],
            # ghost_program is covered by nothing -> the P3 coverage finding
            registered={"decode_step": {}, "ghost_program": {}},
        )
        ghosts = [f for f in fs if f.check == "unaudited-entrypoint"]
        assert [f.target for f in ghosts] == ["ghost_program"]
        assert ghosts[0].severity == "P3"


@pytest.fixture(scope="module")
def audited_model():
    from accelerate_tpu.models import DecoderConfig, DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params

    cfg = DecoderConfig.tiny(max_seq_len=64)
    model = DecoderLM(cfg)
    variables = model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)
    params, _ = unbox_params(variables["params"])
    return model, cfg, params


class TestEngineWarmupSetZeroFalsePositives:
    """The acceptance half of the golden corpus: the SAME checks that
    flag every seeded violation must emit nothing over the engine's real
    program set — paged, flat, and donation-on."""

    def _engine(self, audited_model, **kw):
        from accelerate_tpu.serving import ServingEngine

        model, cfg, params = audited_model
        kw.setdefault("num_slots", 2)
        kw.setdefault("max_cache_len", 64)
        kw.setdefault("prefill_chunks", (4, 8))
        return ServingEngine(model, params, **kw)

    def test_paged_warmup_set_clean(self, audited_model):
        eng = self._engine(audited_model, page_size=8)
        eng.warmup()
        fs = pa.audit_engine(eng)
        assert fs == [], [f.to_dict() for f in fs]
        names = {pa.EntrypointSpec.normalize(s).name
                 for s in eng.audit_entrypoints()}
        # the full warmup program set is enumerated
        assert {"ragged_prefill_8", "decode_step", "table_set_row",
                "table_set_entry", "page_fork"} <= names

    def test_default_engine_clean(self, audited_model):
        eng = self._engine(audited_model)
        fs = pa.audit_engine(eng)
        assert fs == [], [f.to_dict() for f in fs]

    def test_donation_sets_complete_with_donation_on(self, audited_model):
        # trace-only: donate=True never executes here, so the CPU sim's
        # warn-and-copy behavior is irrelevant — the audit checks that
        # every aval-matched buffer IS in the declared donate sets
        eng = self._engine(audited_model, page_size=8, donate=True)
        fs = pa.audit_engine(eng)
        assert fs == [], [f.to_dict() for f in fs]

    def test_corrupted_donation_set_is_caught(self, audited_model):
        """Teeth check: strip the arena from decode_step's donation set
        and the auditor must flag exactly the donation-miss the real
        engine avoids."""
        eng = self._engine(audited_model, page_size=8, donate=True)
        specs = [s for s in eng.audit_entrypoints()
                 if s["name"] == "decode_step"]
        assert specs and specs[0]["donate"]
        specs[0]["donate"] = tuple(d for d in specs[0]["donate"] if d != 1)
        fs = pa.audit_entrypoints(specs)
        misses = [f for f in fs if f.check == "donation-miss"]
        assert len(misses) == 1 and misses[0].detail["arg"] == 1
        assert misses[0].severity == "P1"


class TestAuditCLI:
    def _main(self, argv):
        from accelerate_tpu.commands.accelerate_cli import main

        return main(argv)

    def test_host_only_clean_exit_zero(self, capsys):
        rc = self._main(["audit", "--host-only", "--json"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert payload["summary"]["findings_p1"] == 0

    def test_unbaselined_p1_exits_nonzero(self, capsys, tmp_path):
        rc = self._main([
            "audit", "--host-only", "--root", REPO,
            "--paths", "tests/audit_fixtures",
            "--baseline", str(tmp_path / "none.json"), "--json",
        ])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert payload["summary"]["findings_p1"] >= 4
        # fingerprints key on the repo-relative path, which differs from
        # the lint_file golden targets here — the CLASS set is the contract
        got = sorted((f["check"], f["severity"]) for f in payload["findings"])
        assert got == sorted(GOLDEN_HOST.values())

    def test_update_baseline_then_clean_with_justification(self, capsys, tmp_path):
        base = str(tmp_path / "base.json")
        args = ["audit", "--host-only", "--root", REPO,
                "--paths", "tests/audit_fixtures", "--baseline", base]
        rc = self._main(args + ["--update-baseline",
                                "--justify", "golden corpus: deliberate"])
        assert rc == 0
        capsys.readouterr()
        rc = self._main(args)
        out = capsys.readouterr().out
        assert rc == 0
        assert "golden corpus: deliberate" in out
        assert "baselined" in out
        # update requires a justification
        rc = self._main(args + ["--update-baseline"])
        assert rc == 2

    def test_stale_baseline_entries_reported(self, capsys, tmp_path):
        base = Baseline()
        base.add(Finding(check="ghost", severity="P1", target="gone.py",
                         message="m"), "was fixed long ago")
        path = str(tmp_path / "stale.json")
        base.save(path)
        rc = self._main(["audit", "--host-only", "--root", REPO,
                         "--baseline", path, "--json"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert list(payload["stale_baseline"]) == [
            fmod.fingerprint("ghost", "gone.py", "")
        ]

    def test_repo_gate_full_audit_clean(self, capsys, tmp_path):
        """THE CI gate: both passes over the repo's own host modules and
        registered entry points exit 0 modulo the checked-in baseline.
        In-process (jax is already up) so the tier-1 bill is the traces,
        not a cold interpreter."""
        out_dir = str(tmp_path / "artifacts")
        rc = self._main(["audit", "--root", REPO, "--out", out_dir, "--json"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0, payload
        assert payload["summary"]["findings_p1"] == 0
        # the program pass must actually TRACE everything — a spec that
        # degrades to audit-trace-error is a silently-skipped audit
        assert payload["summary"]["findings_total"] == 0, payload["findings"]
        assert [n for n in payload["notes"] if "program audit" in n]
        saved = json.load(open(os.path.join(out_dir, "audit.json")))
        assert saved["summary"] == payload["summary"]


class TestReportAuditIntegration:
    def _write_audit(self, d, findings):
        payload = {
            "findings": [f.to_dict() for f in findings],
            "suppressed": [],
            "summary": fmod.summarize(findings),
        }
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "audit.json"), "w") as fh:
            json.dump(payload, fh)

    def test_report_renders_audit_section(self, capsys, tmp_path):
        from accelerate_tpu.commands.accelerate_cli import main

        d = str(tmp_path / "t")
        self._write_audit(d, [Finding(
            check="donation-miss", severity="P1", target="decode_step",
            anchor="arg1", message="arena not donated",
        )])
        rc = main(["report", d])
        out = capsys.readouterr().out
        assert rc == 0
        assert "static audit: 1 active finding(s) (1 P1)" in out
        assert "donation-miss" in out and "decode_step" in out

    def test_diff_trips_on_new_p1_fingerprint(self, capsys, tmp_path):
        """A NEW P1 between two runs must trip `--fail` even when the
        count metrics alone would not be shared/flagged."""
        from accelerate_tpu.commands.accelerate_cli import main
        from accelerate_tpu.commands.report import collect_diff_metrics

        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        self._write_audit(a, [])
        new = Finding(check="lock-inversion", severity="P1",
                      target="telemetry/x.py", anchor="A<->B", message="m")
        self._write_audit(b, [new])
        ma, mb = collect_diff_metrics(a), collect_diff_metrics(b)
        assert ma["audit/findings_p1"] == 0.0
        assert mb[f"audit/p1/{new.fingerprint}"] == 1.0
        rc = main(["report", "--diff", a, b, "--fail"])
        out = capsys.readouterr().out
        assert rc == 1
        assert f"audit/p1/{new.fingerprint}" in out

    def test_diff_clean_when_same_findings(self, capsys, tmp_path):
        from accelerate_tpu.commands.accelerate_cli import main

        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        same = Finding(check="lock-inversion", severity="P1",
                       target="telemetry/x.py", anchor="A<->B", message="m")
        self._write_audit(a, [same])
        self._write_audit(b, [same])
        rc = main(["report", "--diff", a, b, "--fail"])
        capsys.readouterr()
        assert rc == 0
