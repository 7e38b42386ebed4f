"""distlint (lint/distlint.py DV201-DV205) + core/knobs.py + the
sharding-table semantic checker (tools/shard_check.py) + the lint
cache: per-rule positive/negative fixtures, suppression/baseline
interplay, the repo self-lint gate, knob-registry round-trips (the
HOLD_MS garbage regression included), the DV204-backed emitter walk
that replaced the per-PR drift tests, and shard_check's
pass/fail/zero-compile contracts.
"""
from __future__ import annotations

import json
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from deep_vision_tpu.core import knobs
from deep_vision_tpu.lint import lint_source
from deep_vision_tpu.lint.__main__ import main as lint_main
from deep_vision_tpu.lint.cache import LintCache, pack_fingerprint
from deep_vision_tpu.lint.rules import RULES

REPO_ROOT = Path(__file__).resolve().parents[1]


def run(src: str, **kw):
    kept, _ = lint_source(textwrap.dedent(src), "fixture.py", **kw)
    return kept


def codes(src: str, **kw):
    return [f.code for f in run(src, **kw)]


# -- DV201 hardcoded-platform-check -------------------------------------------

class TestDV201:
    def test_default_backend_comparison_flags(self):
        found = run("""
            import jax

            def pick():
                return jax.default_backend() == "tpu"
        """, select=["DV201"])
        assert [f.code for f in found] == ["DV201"]
        assert "core/backend.py" in found[0].message

    def test_device_platform_and_membership_flag(self):
        assert codes("""
            def route(device):
                if device.platform != "cpu":
                    return 1
                return platform in ("tpu", "gpu")
        """, select=["DV201"]) == ["DV201", "DV201"]

    def test_sanctioned_module_is_exempt(self):
        src = textwrap.dedent("""
            import jax

            def is_tpu():
                return jax.default_backend() == "tpu"
        """)
        kept, _ = lint_source(src, "deep_vision_tpu/core/backend.py",
                              select=["DV201"])
        assert kept == []

    def test_recording_platform_is_clean(self):
        # telemetry sites that only RECORD the platform never compare
        assert codes("""
            import jax

            def fingerprint(journal):
                journal.write("note", platform=jax.default_backend())
        """, select=["DV201"]) == []

    def test_non_platform_string_comparison_is_clean(self):
        assert codes("""
            def check(mode):
                return mode == "fast"
        """, select=["DV201"]) == []


# -- DV202 unbounded-collective -----------------------------------------------

class TestDV202:
    def test_raw_multihost_utils_flags(self):
        found = run("""
            from jax.experimental import multihost_utils

            def sync():
                multihost_utils.sync_global_devices("epoch")
        """, select=["DV202"])
        assert [f.code for f in found] == ["DV202"]
        assert "deadline-bounded" in found[0].message

    def test_bare_imported_collective_flags(self):
        assert codes("""
            from jax.experimental.multihost_utils import process_allgather

            def gather(x):
                return process_allgather(x)
        """, select=["DV202"]) == ["DV202"]

    def test_sanctioned_wrappers_are_exempt(self):
        src = textwrap.dedent("""
            from jax.experimental import multihost_utils

            def barrier(tag):
                multihost_utils.sync_global_devices(tag)
        """)
        for sanctioned in ("deep_vision_tpu/parallel/multihost.py",
                           "deep_vision_tpu/resilience/rendezvous.py"):
            kept, _ = lint_source(src, sanctioned, select=["DV202"])
            assert kept == []

    def test_device_collectives_are_not_flagged(self):
        # lax.psum inside shard_map is a different animal (deadlines
        # do not apply to device-level collectives)
        assert codes("""
            import jax

            def reduce(x):
                return jax.lax.psum(x, axis_name="data")
        """, select=["DV202"]) == []


# -- DV203 unregistered-env-knob ----------------------------------------------

class TestDV203:
    def test_raw_environ_read_flags(self):
        found = run("""
            import os

            def deadline():
                return float(os.environ.get("DVT_COLLECTIVE_DEADLINE_S",
                                            "600"))
        """, select=["DV203"])
        assert [f.code for f in found] == ["DV203"]
        assert "core/knobs.py" in found[0].message

    def test_getenv_and_subscript_flag(self):
        assert codes("""
            import os

            def reads():
                a = os.getenv("DVT_TELEMETRY")
                b = os.environ["DVT_LOCKSMITH"]
                return a, b
        """, select=["DV203"]) == ["DV203", "DV203"]

    def test_constant_routed_read_flags(self):
        # ENV_SPEC = "DVT_FAULT_SPEC" then os.environ.get(ENV_SPEC)
        assert codes("""
            import os

            ENV_SPEC = "DVT_FAULT_SPEC"

            def spec():
                return os.environ.get(ENV_SPEC)
        """, select=["DV203"]) == ["DV203"]

    def test_helper_with_unregistered_knob_flags(self):
        found = run("""
            from deep_vision_tpu.core import knobs

            def read():
                return knobs.get_int("DVT_TOTALLY_NEW_KNOB")
        """, select=["DV203"])
        assert [f.code for f in found] == ["DV203"]
        assert "DVT_TOTALLY_NEW_KNOB" in found[0].message

    def test_helper_with_registered_knob_is_clean(self):
        assert codes("""
            from deep_vision_tpu.core import knobs

            def read():
                return knobs.get_float("DVT_COLLECTIVE_DEADLINE_S")
        """, select=["DV203"]) == []

    def test_non_dvt_env_and_writes_are_clean(self):
        assert codes("""
            import os

            def other():
                os.environ["DVT_FAULT_SPEC"] = "spec"   # a WRITE
                return os.environ.get("JAX_PLATFORMS")
        """, select=["DV203"]) == []

    def test_knobs_module_itself_is_exempt(self):
        src = "import os\nV = os.environ.get('DVT_LOCKSMITH')\n"
        kept, _ = lint_source(src, "deep_vision_tpu/core/knobs.py",
                              select=["DV203"])
        assert kept == []


# -- DV204 journal-schema-drift -----------------------------------------------

class TestDV204:
    def test_unschemad_event_flags(self):
        found = run("""
            def emit(journal):
                journal.write("zz_unheard_of_event", value=1)
        """, select=["DV204"])
        assert [f.code for f in found] == ["DV204"]
        assert "--strict schema" in found[0].message

    def test_schemad_event_and_constant_routed_are_clean(self):
        assert codes("""
            EVENT_LOST = "host_lost"

            def emit(journal):
                journal.write("step", step=1)
                journal.write(EVENT_LOST, host="h", generation=0)
        """, select=["DV204"]) == []

    def test_dynamic_event_outside_wrapper_flags(self):
        found = run("""
            def emit(journal, name):
                journal.write(name, value=1)
        """, select=["DV204"])
        assert [f.code for f in found] == ["DV204"]
        assert "dynamic" in found[0].message

    def test_forwarding_wrapper_checks_call_sites(self):
        # the wrapper's own dynamic write is plumbing; its literal call
        # sites are the emitters — one good, one unschema'd
        found = run("""
            class Service:
                def __init__(self, journal):
                    self.journal = journal

                def _event(self, event, **fields):
                    if self.journal is not None:
                        self.journal.write(event, **fields)

                def work(self):
                    self._event("step", step=1)
                    self._event("zz_not_schemad", x=2)
        """, select=["DV204"])
        assert [f.code for f in found] == ["DV204"]
        assert "zz_not_schemad" in found[0].message

    def test_unrelated_write_methods_are_clean(self):
        assert codes("""
            def save(fh):
                fh.write("zz_unheard_of_event")
        """, select=["DV204"]) == []


EMITTER_FILES = sorted(
    str(p.relative_to(REPO_ROOT))
    for d in ("deep_vision_tpu", "tools")
    for p in (REPO_ROOT / d).rglob("*.py")
    if re.search(r"(journal|_journal|self)\.write\(", p.read_text())
) + ["train.py"]


@pytest.mark.parametrize("relpath", EMITTER_FILES)
def test_every_emitter_event_is_schemad(relpath):
    """The DV204-backed walk that replaced the per-PR emitter-vs-schema
    drift tests: every file that writes journal rows lints clean under
    DV204 — each literal event it emits has a check_journal --strict
    schema (suppressed sites carry an inline reason)."""
    src = (REPO_ROOT / relpath).read_text()
    kept, _ = lint_source(src, relpath, select=["DV204"])
    assert kept == [], [f.render() for f in kept]


def test_injected_unschemad_emitter_fails_lint(tmp_path, capsys):
    """The negative half: a fresh emitter with no schema FAILS the gate
    (exit 1), proving DV204 has teeth end-to-end through the CLI."""
    bad = tmp_path / "new_emitter.py"
    bad.write_text(textwrap.dedent("""
        def emit(journal):
            journal.write("zz_new_subsystem_started", pid=1)
    """))
    rc = lint_main([str(bad), "--config",
                    str(REPO_ROOT / "pyproject.toml"), "--no-cache"])
    capsys.readouterr()
    assert rc == 1


# -- DV205 pspec-table-hygiene ------------------------------------------------

class TestDV205:
    def test_curated_shape_is_clean(self):
        assert codes("""
            from deep_vision_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
            from deep_vision_tpu.parallel.shardmap import ShardingRules

            BASE = ShardingRules(
                name="base",
                rules=(
                    ("*.Dense_*.kernel", (None, MODEL_AXIS)),
                    ("*.hyperparams.*", ()),
                    ("*", ()),
                ),
            )
            EXTENDED = ShardingRules(
                name="ext",
                rules=(
                    ("*.Moe_*.kernel", (None, "model")),
                ) + BASE.rules,
            )
        """, select=["DV205"]) == []

    def test_unknown_axis_flags(self):
        found = run("""
            from deep_vision_tpu.parallel.shardmap import ShardingRules

            T = ShardingRules(
                name="t",
                rules=(
                    ("*.kernel", (None, "modle")),
                    ("*", ()),
                ),
            )
        """, select=["DV205"])
        assert [f.code for f in found] == ["DV205"]
        assert "modle" in found[0].message

    def test_missing_catch_all_flags(self):
        found = run("""
            from deep_vision_tpu.parallel.shardmap import ShardingRules

            T = ShardingRules(
                name="t",
                rules=(
                    ("*.kernel", (None, "model")),
                    ("*.bias", ("model",)),
                ),
            )
        """, select=["DV205"])
        assert [f.code for f in found] == ["DV205"]
        assert "catch-all" in found[0].message

    def test_non_literal_pattern_and_table_flag(self):
        found = run("""
            from deep_vision_tpu.parallel.shardmap import ShardingRules

            pat = make_pattern()
            T = ShardingRules(
                name="t",
                rules=(
                    (pat, (None, "model")),
                    ("*", ()),
                ),
            )
            U = ShardingRules(name="u", rules=build_rules())
        """, select=["DV205"])
        assert [f.code for f in found] == ["DV205", "DV205"]
        assert "literal" in found[0].message

    def test_unrelated_calls_are_clean(self):
        assert codes("""
            T = dict(rules=(("*", "x"),))
        """, select=["DV205"]) == []


# -- pack integration: suppression, baseline, self-lint ------------------------

DV201_SRC = """
import jax


def pick():
    return jax.default_backend() == "tpu"{pragma}
"""


def test_dv2xx_inline_suppression():
    dirty = textwrap.dedent(DV201_SRC.format(pragma=""))
    kept, dropped = lint_source(dirty, "mod.py", select=["DV201"])
    assert [f.code for f in kept] == ["DV201"]
    clean = textwrap.dedent(DV201_SRC.format(
        pragma="  # jaxlint: disable=DV201 -- fixture"))
    kept, dropped = lint_source(clean, "mod.py", select=["DV201"])
    assert kept == []
    assert [f.code for f in dropped] == ["DV201"]


def test_dv2xx_baseline_interplay(tmp_path, capsys):
    mod = tmp_path / "mod.py"
    mod.write_text(textwrap.dedent(DV201_SRC.format(pragma="")))
    (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""
        [tool.jaxlint]
        paths = ["mod.py"]
        baseline = "baseline.json"
    """))
    pp = str(tmp_path / "pyproject.toml")
    assert lint_main(["--config", pp]) == 1
    capsys.readouterr()
    assert lint_main(["--config", pp, "--write-baseline"]) == 0
    capsys.readouterr()
    assert lint_main(["--config", pp]) == 0
    # line drift must not resurrect the accepted finding
    mod.write_text("# a new leading comment\n" + mod.read_text())
    assert lint_main(["--config", pp]) == 0


def test_dv2xx_rules_registered():
    for code in ("DV201", "DV202", "DV203", "DV204", "DV205"):
        assert code in RULES
        name, severity, check, doc = RULES[code]
        assert severity == "error" and callable(check)


def test_repo_self_lint_dist_clean(capsys):
    """The shipped tree is clean under the distributed pack — true
    positives were FIXED (platform checks routed through core/backend,
    knobs onto the registry), not baselined; the committed baseline
    stays empty. The DV201-DV205 acceptance gate."""
    rc = lint_main(["--config", str(REPO_ROOT / "pyproject.toml"),
                    "--select", "DV201,DV202,DV203,DV204,DV205",
                    "--no-cache"])
    out = capsys.readouterr().out
    assert rc == 0, f"distlint found new violations:\n{out}"
    baseline = json.loads(
        (REPO_ROOT / ".jaxlint-baseline.json").read_text())
    assert baseline["findings"] == [], \
        "the committed baseline must stay empty"


def test_dv2xx_in_json_output(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(DV201_SRC.format(pragma="")))
    rc = lint_main([str(bad), "--config",
                    str(REPO_ROOT / "pyproject.toml"),
                    "--format", "json", "--no-cache"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["summary"]["failed"] is True
    assert [f["code"] for f in doc["findings"]] == ["DV201"]


# -- the knob registry ---------------------------------------------------------

class TestKnobs:
    def test_typed_round_trips(self, monkeypatch):
        monkeypatch.setenv("DVT_FLASH_MIN_TOKENS", "256")
        assert knobs.get_int("DVT_FLASH_MIN_TOKENS") == 256
        monkeypatch.setenv("DVT_COLLECTIVE_DEADLINE_S", "12.5")
        assert knobs.get_float("DVT_COLLECTIVE_DEADLINE_S") == 12.5
        monkeypatch.setenv("DVT_LOCKSMITH", "on")
        assert knobs.get_flag("DVT_LOCKSMITH") is True
        monkeypatch.setenv("DVT_LOCKSMITH", "0")
        assert knobs.get_flag("DVT_LOCKSMITH") is False
        monkeypatch.setenv("DVT_NMS_IMPL", "pallas")
        assert knobs.get_choice("DVT_NMS_IMPL") == "pallas"
        monkeypatch.setenv("DVT_EXCACHE", "/tmp/x")
        assert knobs.get_str("DVT_EXCACHE") == "/tmp/x"

    def test_unset_and_empty_mean_default(self, monkeypatch):
        monkeypatch.delenv("DVT_FLASH_MIN_TOKENS", raising=False)
        assert knobs.get_int("DVT_FLASH_MIN_TOKENS") == 1024
        monkeypatch.setenv("DVT_FLASH_MIN_TOKENS", "   ")
        assert knobs.get_int("DVT_FLASH_MIN_TOKENS") == 1024
        # explicit default overrides the registered one
        assert knobs.get_int("DVT_FLASH_MIN_TOKENS", default=None) is None

    def test_mistype_raises_naming_the_knob(self, monkeypatch):
        monkeypatch.setenv("DVT_FLASH_MIN_TOKENS", "fast")
        with pytest.raises(knobs.KnobError, match="DVT_FLASH_MIN_TOKENS"):
            knobs.get_int("DVT_FLASH_MIN_TOKENS")
        monkeypatch.setenv("DVT_NMS_IMPL", "LAX")  # no normalization
        with pytest.raises(knobs.KnobError, match="DVT_NMS_IMPL"):
            knobs.get_choice("DVT_NMS_IMPL")
        monkeypatch.setenv("DVT_LOCKSMITH", "maybe")
        with pytest.raises(knobs.KnobError, match="DVT_LOCKSMITH"):
            knobs.get_flag("DVT_LOCKSMITH")

    def test_unregistered_and_wrong_kind_raise(self):
        with pytest.raises(knobs.KnobError, match="not a registered"):
            knobs.get_int("DVT_NO_SUCH_KNOB")
        with pytest.raises(knobs.KnobError, match="get_float"):
            knobs.get_int("DVT_COLLECTIVE_DEADLINE_S")

    def test_locksmith_garbage_threshold_raises(self, monkeypatch):
        """The regression that motivated the registry: HOLD_MS/WAIT_MS
        used to feed float() inside a bare try/except — garbage silently
        meant 1000ms. Now arming with garbage RAISES, naming the knob."""
        from deep_vision_tpu.obs import locksmith

        monkeypatch.setenv("DVT_LOCKSMITH", "1")
        monkeypatch.setenv("DVT_LOCKSMITH_HOLD_MS", "oops")
        with pytest.raises(knobs.KnobError, match="DVT_LOCKSMITH_HOLD_MS"):
            locksmith.arm_from_env()
        monkeypatch.setenv("DVT_LOCKSMITH_HOLD_MS", "250")
        monkeypatch.setenv("DVT_LOCKSMITH_WAIT_MS", "not-a-number")
        with pytest.raises(knobs.KnobError, match="DVT_LOCKSMITH_WAIT_MS"):
            locksmith.arm_from_env()
        monkeypatch.setenv("DVT_LOCKSMITH_WAIT_MS", "250")
        san = locksmith.arm_from_env()
        try:
            assert san is not None
        finally:
            locksmith.disarm()

    def test_knobs_import_is_stdlib_only(self):
        """rendezvous/faults read knobs before paying the jax import —
        the registry must never drag jax/flax in."""
        code = ("import sys\n"
                "from deep_vision_tpu.core import knobs\n"
                "assert 'jax' not in sys.modules, 'knobs imported jax'\n"
                "assert 'flax' not in sys.modules, 'knobs imported flax'\n"
                "assert knobs.get_int('DVT_FLASH_MIN_TOKENS') == 1024\n")
        subprocess.run([sys.executable, "-c", code], check=True,
                       cwd=str(REPO_ROOT))

    def test_readme_lists_every_knob(self):
        """The README 'Environment knobs' table cannot drift from the
        registry: every registered name appears, and the table carries
        no DVT_* name the registry does not declare."""
        readme = (REPO_ROOT / "README.md").read_text()
        section = readme.split("## Environment knobs", 1)[1]
        section = section.split("\n## ", 1)[0]
        for name in knobs.KNOBS:
            assert f"`{name}`" in section, f"README is missing {name}"
        documented = set(re.findall(r"`(DVT_[A-Z0-9_]+)`", section))
        assert documented == set(knobs.KNOBS)

    def test_cli_knob_table(self, capsys):
        assert lint_main(["--knobs"]) == 0
        out = capsys.readouterr().out
        for name in knobs.KNOBS:
            assert name in out
        assert "choice(lax|pallas)" in out


# -- the incremental lint cache ------------------------------------------------

class TestLintCache:
    SRC = "import jax\n\ndef f():\n    return jax.default_backend() == 'tpu'\n"

    def test_hit_returns_identical_verdicts(self, tmp_path):
        cache = LintCache(str(tmp_path / "c"),
                          pack_fingerprint(["DV201"], root=str(REPO_ROOT)))
        kept, dropped = lint_source(self.SRC, "m.py", select=["DV201"])
        assert cache.get("m.py", self.SRC) is None  # cold
        cache.put("m.py", self.SRC, kept, dropped)
        got = cache.get("m.py", self.SRC)
        assert got is not None and got[0] == kept and got[1] == dropped
        assert cache.hits == 1 and cache.misses == 1

    def test_content_and_pack_changes_miss(self, tmp_path):
        fp = pack_fingerprint(["DV201"], root=str(REPO_ROOT))
        cache = LintCache(str(tmp_path / "c"), fp)
        cache.put("m.py", self.SRC, [], [])
        assert cache.get("m.py", self.SRC + "# edit\n") is None
        # a different enabled-rule set is a different fingerprint
        fp2 = pack_fingerprint(["DV201", "DV202"], root=str(REPO_ROOT))
        assert fp2 != fp
        assert LintCache(str(tmp_path / "c"), fp2).get(
            "m.py", self.SRC) is None

    def test_corrupt_entry_is_a_miss_not_a_crash(self, tmp_path):
        cache = LintCache(str(tmp_path / "c"),
                          pack_fingerprint(["DV201"], root=str(REPO_ROOT)))
        cache.put("m.py", self.SRC, [], [])
        entry = next(Path(str(tmp_path / "c")).iterdir())
        entry.write_text("{not json")
        assert cache.get("m.py", self.SRC) is None

    def test_cli_cache_round_trip(self, tmp_path, capsys):
        mod = tmp_path / "mod.py"
        mod.write_text(textwrap.dedent(DV201_SRC.format(pragma="")))
        (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""
            [tool.jaxlint]
            paths = ["mod.py"]
            baseline = "baseline.json"
        """))
        pp = str(tmp_path / "pyproject.toml")
        assert lint_main(["--config", pp]) == 1          # cold, cached
        capsys.readouterr()
        assert (tmp_path / "artifacts" / "lint_cache").is_dir()
        assert lint_main(["--config", pp]) == 1          # warm, same rc
        capsys.readouterr()
        # the fix invalidates the entry and the gate goes green
        mod.write_text("x = 1\n")
        assert lint_main(["--config", pp]) == 0
        capsys.readouterr()
        assert lint_main(["--config", pp, "--no-cache"]) == 0


# -- shard_check: the semantic half -------------------------------------------

@pytest.fixture(scope="module")
def shard_check():
    from deep_vision_tpu.tools import shard_check as sc

    return sc


class TestShardCheck:
    def test_all_curated_tables_pass(self, shard_check):
        for family in shard_check.FAMILIES:
            report = shard_check.check_family(family)
            assert report["ok"], report
            assert report["sharded"] >= report["min_sharded"]
            assert report["errors"] == []
            assert report["dead"] == [], report["dead"]

    def test_runs_with_zero_compiles_and_zero_device_arrays(
            self, shard_check):
        """The whole audit is abstract: eval_shape over
        ShapeDtypeStruct inputs must not trigger a single backend
        compile (the stepclock monitoring counter is the proof)."""
        from deep_vision_tpu.obs.stepclock import recompile_count

        before = recompile_count()
        report = shard_check.check_family("vit")
        assert report["ok"]
        assert recompile_count() == before

    def test_gutted_table_fails_naming_the_floor(self, shard_check):
        from deep_vision_tpu.parallel.shardmap import ShardingRules

        gutted = ShardingRules(
            name="vit",
            # jaxlint: disable=DV205 -- deliberately gutted test subject
            rules=(("*", ()),),
            min_sharded=12,
        )
        report = shard_check.check_family("vit", rules=gutted)
        assert not report["ok"] and not report["floor_ok"]
        assert report["sharded"] == 0
        rendered = shard_check.render_report(report)
        assert "FAIL" in rendered and "coverage floor" in rendered

    def test_shadowed_and_dead_rules_reported(self, shard_check):
        from deep_vision_tpu.parallel.mesh import MODEL_AXIS
        from deep_vision_tpu.parallel.shardmap import ShardingRules

        table = ShardingRules(
            name="vit",
            rules=(
                ("*.kernel", (None, MODEL_AXIS)),
                # shadowed: every Dense kernel already matched above
                ("*.Dense_*.kernel", (None, MODEL_AXIS)),
                # dead: no leaf path contains 'Conv' in a ViT
                ("*.Conv_*.kernel", (None, MODEL_AXIS)),
                ("*", ()),
            ),
            min_sharded=1,
        )
        report = shard_check.check_family("vit", rules=table)
        assert "*.Dense_*.kernel" in report["shadowed"]
        assert "*.Conv_*.kernel" in report["dead"]
        # shadow/dead are report-only; the floor holds, so the table
        # passes
        assert report["ok"]

    def test_unknown_axis_is_an_error(self, shard_check):
        from deep_vision_tpu.parallel.shardmap import ShardingRules

        report = shard_check.check_family("vit", rules=ShardingRules(
            name="vit",
            # jaxlint: disable=DV205 -- deliberately bad test subject
            rules=(("*.kernel", (None, "bogus_axis")), ("*", ())),
        ))
        assert not report["ok"]
        assert any("bogus_axis" in e for e in report["errors"])

    def test_cli_pass_and_json(self, shard_check, capsys):
        assert shard_check.main([]) == 0
        out = capsys.readouterr().out
        assert out.count("PASS") == 3 and "FAIL" not in out
        assert shard_check.main(["--family", "vit",
                                 "--format", "json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["failed"] is False
        assert doc["reports"][0]["family"] == "vit"

    def test_cli_fails_on_broken_family(self, shard_check, capsys,
                                        monkeypatch):
        from deep_vision_tpu.parallel.shardmap import (
            FAMILY_RULES,
            ShardingRules,
        )

        gutted = dict(FAMILY_RULES)
        gutted["moe"] = ShardingRules(
            name="moe",
            # jaxlint: disable=DV205 -- deliberately gutted test subject
            rules=(("*", ()),),
            min_sharded=16,
        )
        monkeypatch.setattr("deep_vision_tpu.parallel.shardmap."
                            "FAMILY_RULES", gutted)
        assert shard_check.main([]) == 1
        captured = capsys.readouterr()
        assert "shard_check[moe]: FAIL" in captured.out

    def test_preflight_rung(self, shard_check, monkeypatch):
        from deep_vision_tpu.parallel.shardmap import (
            FAMILY_RULES,
            ShardingRules,
        )
        from deep_vision_tpu.tools.preflight import check_sharding_tables

        r = check_sharding_tables()
        assert r.ok and r.name == "sharding_tables"
        assert "vit" in r.detail and "resnet" in r.detail
        gutted = dict(FAMILY_RULES)
        gutted["vit"] = ShardingRules(
            name="vit",
            # jaxlint: disable=DV205 -- deliberately gutted test subject
            rules=(("*", ()),),
            min_sharded=12,
        )
        monkeypatch.setattr("deep_vision_tpu.parallel.shardmap."
                            "FAMILY_RULES", gutted)
        r = check_sharding_tables()
        assert not r.ok and "vit" in r.detail
