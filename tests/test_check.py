"""Tests for the static contract checker (``repro.check``).

Each rule gets a minimal fixture tree with a seeded violation and an
assertion that ``python -m repro.check`` would exit nonzero on it; the
final test asserts the live repo is check-clean, which is the invariant
CI enforces.
"""

from __future__ import annotations

import json
import textwrap
from pathlib import Path

import pytest

from repro.check import run_checks
from repro.check.__main__ import main
from repro.check.schema import update_fingerprint

REPO_ROOT = Path(__file__).resolve().parents[1]


def write_tree(root: Path, files: dict[str, str]) -> Path:
    for rel, content in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(content))
    return root


def rule_lines(findings, rule):
    return [(f.file, f.line) for f in findings if f.rule == rule]


def messages(findings):
    return "\n".join(f.message for f in findings)


# --- workload-contract ---------------------------------------------------


def workload_fixture(tmp_path: Path) -> Path:
    return write_tree(
        tmp_path,
        {
            "src/repro/kernels/ops.py": """\
                from repro.kernels import badkern as _bad_mod

                PALLAS_OPS = {"badkern": _bad_mod}
            """,
            "src/repro/kernels/badkern.py": """\
                def tune_space():
                    return ({"block": 0},)
            """,
            "src/repro/bench/level0/foo.py": """\
                def register():
                    return Workload(name="foo", pallas_kernel="nope")
            """,
        },
    )


def test_workload_contract_fires(tmp_path):
    root = workload_fixture(tmp_path)
    findings = run_checks(root, rules=["workload-contract"])
    msgs = messages(findings)
    assert "positive int literals" in msgs  # block: 0 in tune_space
    assert "batch_dims" in msgs  # Workload() without batch_dims
    assert "'nope' is not a key" in msgs  # unknown pallas_kernel
    assert main(["--root", str(root), "--rules", "workload-contract"]) == 1


def test_workload_contract_checks_kernel_passed_through_helpers(tmp_path):
    root = workload_fixture(tmp_path)
    (root / "src/repro/bench/level0/foo.py").write_text(
        textwrap.dedent("""\
            def register():
                # Not a Workload() call: kernel rides a construction helper.
                return make_workload(name="foo", pallas_kernel="bogus")
        """)
    )
    findings = run_checks(root, rules=["workload-contract"])
    assert "'bogus' is not a key" in messages(findings)


def test_workload_contract_ignores_strings_in_conditional_test(tmp_path):
    root = workload_fixture(tmp_path)
    (root / "src/repro/kernels/badkern.py").write_text(
        "def tune_space():\n    return ({},)\n"
    )
    (root / "src/repro/bench/level0/foo.py").write_text(
        textwrap.dedent("""\
            def register(impl):
                return Workload(
                    name="foo",
                    batch_dims=(0,),
                    # "other" sits in the test position, not a kernel name.
                    pallas_kernel="badkern" if impl == "other" else None,
                )
        """)
    )
    assert run_checks(root, rules=["workload-contract"]) == []


def test_workload_contract_accepts_optout_and_known_kernel(tmp_path):
    root = workload_fixture(tmp_path)
    (root / "src/repro/bench/level0/foo.py").write_text(
        textwrap.dedent("""\
            def register():
                return Workload(
                    name="foo", batch_dims=None, pallas_kernel="badkern"
                )
        """)
    )
    (root / "src/repro/kernels/badkern.py").write_text(
        "def tune_space():\n    return ({},)\n"
    )
    assert run_checks(root, rules=["workload-contract"]) == []


# --- cache-key -----------------------------------------------------------


def cachekey_fixture(tmp_path: Path) -> Path:
    return write_tree(
        tmp_path,
        {
            "src/repro/core/plan.py": """\
                import dataclasses

                @dataclasses.dataclass(frozen=True)
                class Placement:
                    devices: int
                    mode: str
            """,
            "src/repro/core/engine.py": """\
                class Engine:
                    def _bucket_key(self, spec, preset, placement, impl):
                        return (spec, preset, placement.devices)

                    def load(self, spec):
                        return self.disk_cache.load((spec, "adhoc"), None)
            """,
            "src/repro/core/hlocache.py": """\
                import hashlib

                class HloDiskCache:
                    def _path(self, key):
                        return hashlib.sha256(repr(key[0]).encode()).hexdigest()
            """,
        },
    )


def test_cache_key_fires(tmp_path):
    root = cachekey_fixture(tmp_path)
    findings = run_checks(root, rules=["cache-key"])
    msgs = messages(findings)
    assert "'impl' never reaches the key" in msgs
    assert "omits Placement.mode" in msgs
    assert "not built ad hoc" in msgs
    assert "must not subscript the key" in msgs
    assert main(["--root", str(root), "--rules", "cache-key"]) == 1


def test_cache_key_accepts_builder_bound_keys(tmp_path):
    root = cachekey_fixture(tmp_path)
    (root / "src/repro/core/engine.py").write_text(
        textwrap.dedent("""\
            class Engine:
                def _bucket_key(self, spec, preset, placement, impl, width):
                    base = (spec, preset, placement.devices, placement.mode, impl)
                    return base if width == 1 else base + ("vmap", width)

                def load(self, spec, preset, placement, impl):
                    key = self._bucket_key(spec, preset, placement, impl, 1)

                    def build():
                        # Closure capture of `key` is a legal binding.
                        return self.disk_cache.load(key, None)

                    return build()
        """)
    )
    (root / "src/repro/core/hlocache.py").write_text(
        textwrap.dedent("""\
            import hashlib

            class HloDiskCache:
                def _path(self, key):
                    return hashlib.sha256(repr(key).encode()).hexdigest()
        """)
    )
    assert run_checks(root, rules=["cache-key"]) == []


# --- stage-discipline ----------------------------------------------------


def stage_fixture(tmp_path: Path) -> Path:
    return write_tree(
        tmp_path,
        {
            "src/repro/core/engine.py": """\
                class Engine:
                    def run_one(self, spec):
                        entry = self._stage_measure(spec)
                        return entry
            """,
            "src/repro/core/harness.py": """\
                def time_fn(fn, tracer):
                    tracer.counters.inc("samples", 1)
                    if tracer.enabled:
                        tracer.counters.inc("guarded", 1)
                    return fn()
            """,
        },
    )


def test_stage_discipline_fires(tmp_path):
    root = stage_fixture(tmp_path)
    findings = run_checks(root, rules=["stage-discipline"])
    msgs = messages(findings)
    assert "_stage_measure() called outside a _timed_stage span" in msgs
    assert "without an `if tracer.enabled:` guard" in msgs
    # The guarded inc() on the next line must NOT be flagged.
    hot = [f for f in findings if f.file == "src/repro/core/harness.py"]
    assert len(hot) == 1 and hot[0].line == 2
    assert main(["--root", str(root), "--rules", "stage-discipline"]) == 1


def test_stage_discipline_accepts_timed_calls(tmp_path):
    root = stage_fixture(tmp_path)
    (root / "src/repro/core/engine.py").write_text(
        textwrap.dedent("""\
            class Engine:
                def run_one(self, spec):
                    timings = {}
                    with self._timed_stage("measure", timings):
                        entry = self._stage_measure(spec)
                    return entry

                def _stage_tune(self, spec):
                    # Nested stage calls run inside the caller's span.
                    return self._stage_compile(spec)
        """)
    )
    (root / "src/repro/core/harness.py").write_text(
        "def time_fn(fn):\n    return fn()\n"
    )
    assert run_checks(root, rules=["stage-discipline"]) == []


# --- schema-drift --------------------------------------------------------


RESULTS_V3 = """\
    SCHEMA_VERSION = 3

    class BenchmarkRecord:
        name: str
        us_per_call: float

    class RunMetadata:
        backend: str

    def csv_header():
        return "name,us_per_call"
"""


def test_schema_drift_missing_fingerprint_fires(tmp_path):
    root = write_tree(tmp_path, {"src/repro/core/results.py": RESULTS_V3})
    findings = run_checks(root, rules=["schema-drift"])
    assert "fingerprint is missing" in messages(findings)
    assert main(["--root", str(root), "--rules", "schema-drift"]) == 1


def test_schema_drift_shape_change_without_bump_fires(tmp_path):
    root = write_tree(tmp_path, {"src/repro/core/results.py": RESULTS_V3})
    update_fingerprint(root)
    assert run_checks(root, rules=["schema-drift"]) == []
    # Grow the record without touching SCHEMA_VERSION.
    (root / "src/repro/core/results.py").write_text(
        textwrap.dedent(RESULTS_V3).replace(
            "us_per_call: float", "us_per_call: float\n    extra: int"
        )
    )
    findings = run_checks(root, rules=["schema-drift"])
    assert "without a SCHEMA_VERSION bump" in messages(findings)


def test_schema_drift_bump_requires_regenerated_fingerprint(tmp_path):
    root = write_tree(tmp_path, {"src/repro/core/results.py": RESULTS_V3})
    update_fingerprint(root)
    (root / "src/repro/core/results.py").write_text(
        textwrap.dedent(RESULTS_V3).replace(
            "SCHEMA_VERSION = 3", "SCHEMA_VERSION = 4"
        )
    )
    findings = run_checks(root, rules=["schema-drift"])
    assert "regenerate" in messages(findings)
    update_fingerprint(root)
    assert run_checks(root, rules=["schema-drift"]) == []


def test_schema_drift_csv_header_must_name_record_fields(tmp_path):
    root = write_tree(
        tmp_path,
        {
            "src/repro/core/results.py": textwrap.dedent(RESULTS_V3).replace(
                '"name,us_per_call"', '"name,bogus_column"'
            )
        },
    )
    update_fingerprint(root)
    findings = run_checks(root, rules=["schema-drift"])
    assert "'bogus_column'" in messages(findings)


# --- concurrency ---------------------------------------------------------


SINK_UNLOCKED = """\
    import threading

    class Sink:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []

        def add(self, item):
            self._items.append(item)

        def harvest(self):
            with self._lock:
                out = list(self._items)
                self._items.clear()
            return out
"""


def test_concurrency_fires_on_unlocked_mutation(tmp_path):
    root = write_tree(tmp_path, {"src/repro/serve/sink.py": SINK_UNLOCKED})
    findings = run_checks(root, rules=["concurrency"])
    assert rule_lines(findings, "concurrency") == [
        ("src/repro/serve/sink.py", 9)
    ]
    assert "outside `with self._lock:`" in messages(findings)
    assert main(["--root", str(root), "--rules", "concurrency"]) == 1


def test_concurrency_skips_lockfree_and_locked_classes(tmp_path):
    root = write_tree(
        tmp_path,
        {
            "src/repro/serve/sink.py": textwrap.dedent(SINK_UNLOCKED).replace(
                "        self._items.append(item)",
                "        with self._lock:\n            self._items.append(item)",
            ),
            # No lock attribute: single-owner by design, out of scope.
            "src/repro/serve/tally.py": """\
                class Tally:
                    def __init__(self):
                        self.counts = {}

                    def bump(self, k):
                        self.counts[k] = self.counts.get(k, 0) + 1
            """,
        },
    )
    assert run_checks(root, rules=["concurrency"]) == []


@pytest.mark.parametrize(
    "path, flagged",
    [
        ("src/repro/obs/sink.py", True),
        # Outside serve/ and obs/: the rule does not look there.
        ("src/repro/core/sink.py", False),
    ],
)
def test_concurrency_scope(tmp_path, path, flagged):
    root = write_tree(tmp_path, {path: SINK_UNLOCKED})
    findings = run_checks(root, rules=["concurrency"])
    assert rule_lines(findings, "concurrency") == ([(path, 9)] if flagged else [])


# --- suppression ---------------------------------------------------------


def test_suppression_comment_silences_one_rule(tmp_path):
    root = write_tree(
        tmp_path,
        {
            "src/repro/serve/sink.py": textwrap.dedent(SINK_UNLOCKED).replace(
                "self._items.append(item)",
                "self._items.append(item)  # repro-check: ignore[concurrency]",
            )
        },
    )
    assert run_checks(root, rules=["concurrency"]) == []


def test_suppression_on_preceding_line_and_star(tmp_path):
    root = write_tree(
        tmp_path,
        {
            "src/repro/serve/sink.py": textwrap.dedent(SINK_UNLOCKED).replace(
                "        self._items.append(item)",
                "        # repro-check: ignore[*]\n"
                "        self._items.append(item)",
            )
        },
    )
    assert run_checks(root, rules=["concurrency"]) == []


def test_suppression_for_other_rule_does_not_silence(tmp_path):
    root = write_tree(
        tmp_path,
        {
            "src/repro/serve/sink.py": textwrap.dedent(SINK_UNLOCKED).replace(
                "self._items.append(item)",
                "self._items.append(item)  # repro-check: ignore[cache-key]",
            )
        },
    )
    assert len(run_checks(root, rules=["concurrency"])) == 1


# --- CLI -----------------------------------------------------------------


def test_cli_json_output(tmp_path, capsys):
    root = write_tree(tmp_path, {"src/repro/serve/sink.py": SINK_UNLOCKED})
    code = main(["--root", str(root), "--json"])
    assert code == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["count"] == len(payload["findings"]) == 1
    finding = payload["findings"][0]
    assert finding["rule"] == "concurrency"
    assert finding["file"] == "src/repro/serve/sink.py"
    assert finding["severity"] == "error"


def test_cli_unknown_rule_exits_2(tmp_path, capsys):
    assert main(["--root", str(tmp_path), "--rules", "no-such-rule"]) == 2
    assert "unknown rule" in capsys.readouterr().err


def test_cli_empty_tree_is_green(tmp_path, capsys):
    # Checkers skip when their target files are absent.
    assert main(["--root", str(tmp_path)]) == 0
    assert "0 findings" in capsys.readouterr().out


def test_cli_update_fingerprint_roundtrip(tmp_path, capsys):
    root = write_tree(tmp_path, {"src/repro/core/results.py": RESULTS_V3})
    assert main(["--root", str(root), "--update-schema-fingerprint"]) == 0
    capsys.readouterr()
    fp = root / "src/repro/check/schema_fingerprint.json"
    committed = json.loads(fp.read_text())
    assert committed["schema_version"] == 3
    assert committed["record_fields"] == ["name", "us_per_call"]
    assert committed["csv_header"] == "name,us_per_call"


# --- the live repo -------------------------------------------------------


def test_live_repo_is_check_clean():
    findings = run_checks(REPO_ROOT)
    assert findings == [], "\n" + "\n".join(f.format() for f in findings)


def test_live_repo_fingerprint_is_current():
    # The committed fingerprint must match what --update-schema-fingerprint
    # would write today, byte for byte.
    from repro.check.core import Context
    from repro.check.schema import FINGERPRINT_FILE, compute_schema

    committed = json.loads((REPO_ROOT / FINGERPRINT_FILE).read_text())
    assert committed == compute_schema(Context(REPO_ROOT))


@pytest.mark.parametrize(
    "rule",
    [
        "workload-contract",
        "cache-key",
        "stage-discipline",
        "schema-drift",
        "concurrency",
    ],
)
def test_every_rule_is_registered(rule):
    from repro.check import all_checkers

    assert rule in {c.rule for c in all_checkers()}
