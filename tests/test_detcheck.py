"""tools/detcheck — the static-analysis gate that enforces the SEC
invariants at lint time.

Three layers of proof here:

1. per-rule fixtures — a violating snippet fires the rule, the
   compliant twin (sorted() sanitizer, exactness guard, _warn helper,
   seeded RNG) does not;
2. suppression lifecycle — a reasoned allow silences, a reasonless
   allow is SUP001, a stale allow is SUP002;
3. seeded regressions on the *real* tree — detcheck passes on
   src/repro as-is, and re-introducing a fixed violation (dropping a
   wire-registry row, the engine's exactness guard, the trust shim's
   _warn helper, an ANALYSIS.md catalog row) makes the pass fail.
"""
from __future__ import annotations

import json
import shutil
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tools.detcheck import cli  # noqa: E402
from tools.detcheck.core import RULES, run  # noqa: E402


def check(tmp_path, code, tier="deterministic", name="snippet.py"):
    """Run detcheck on one snippet; returns the fired rule ids."""
    f = tmp_path / name
    f.write_text(textwrap.dedent(code))
    report = run([f], root=tmp_path, default_tier=tier)
    return [v.rule for v in report.violations]


# ------------------------------------------------------------------ DET ---


def test_det001_wall_clock_fires_in_deterministic_tier(tmp_path):
    code = """
        import time
        def stamp():
            return time.time()
    """
    assert "DET001" in check(tmp_path, code)


def test_det001_silent_in_environment_tier(tmp_path):
    code = """
        import time
        def stamp():
            return time.time()
    """
    assert check(tmp_path, code, tier="environment") == []


def test_det001_injected_clock_reference_ok(tmp_path):
    # passing time.monotonic as an injectable default is the approved
    # pattern — only *calls* at module scope are divergence sources
    code = """
        import time
        def probe(clock=time.monotonic):
            return clock()
    """
    assert check(tmp_path, code) == []


def test_det002_global_rng_fires_seeded_generator_ok(tmp_path):
    bad = """
        import random
        import numpy as np
        def jitter():
            return random.random() + np.random.rand()
    """
    fired = check(tmp_path, bad)
    assert fired.count("DET002") == 2
    good = """
        import random
        import numpy as np
        def jitter(seed):
            rng = random.Random(seed)
            gen = np.random.default_rng(seed)
            return rng.random() + gen.random()
    """
    assert check(tmp_path, good) == []


def test_det003_constant_jax_key_fires_derived_ok(tmp_path):
    bad = """
        import jax
        def noise(shape):
            return jax.random.normal(jax.random.PRNGKey(0), shape)
    """
    assert "DET003" in check(tmp_path, bad)
    good = """
        import jax
        def noise(seed, shape):
            key = jax.random.PRNGKey(seed)
            return jax.random.normal(jax.random.fold_in(key, 1), shape)
    """
    assert check(tmp_path, good) == []


def test_det004_id_and_hash_fire_dunder_hash_exempt(tmp_path):
    bad = """
        def bucket(entry, n):
            return (id(entry) + hash(entry.eid)) % n
    """
    fired = check(tmp_path, bad)
    assert fired.count("DET004") == 2
    good = """
        class Entry:
            def __hash__(self):
                return hash((self.eid, self.root))
    """
    assert check(tmp_path, good) == []


def test_det005_unordered_set_into_digest_fires(tmp_path):
    code = """
        import hashlib
        def digest(eids):
            pending = set(eids)
            h = hashlib.sha256()
            for e in pending:
                h.update(e.encode())
            return h.hexdigest()
    """
    assert "DET005" in check(tmp_path, code)


def test_det005_sorted_sanitizes_the_taint(tmp_path):
    code = """
        import hashlib
        def digest(eids):
            pending = set(eids)
            h = hashlib.sha256()
            for e in sorted(pending):
                h.update(e.encode())
            return h.hexdigest()
    """
    assert check(tmp_path, code) == []


def test_det005_listdir_into_float_accum_fires(tmp_path):
    code = """
        import os
        def total(d, sizes):
            return sum(sizes[n] for n in os.listdir(d))
    """
    assert "DET005" in check(tmp_path, code)


# ------------------------------------------------------------------ HYG ---


def test_hyg001_unguarded_kernel_put_fires(tmp_path):
    code = """
        def flush(cache, group):
            out, auxs, approximate = _kernel_batch(group)
            for t, o in zip(group, out):
                cache.put(t.key, o, 1)
    """
    assert "HYG001" in check(tmp_path, code)


def test_hyg001_exactness_guard_silences(tmp_path):
    code = """
        def flush(cache, group):
            out, auxs, approximate = _kernel_batch(group)
            for t, o in zip(group, out):
                if not approximate:
                    cache.put(t.key, o, 1)
    """
    assert check(tmp_path, code) == []


def test_hyg001_key_only_taint_is_not_flagged(tmp_path):
    # the cache *key* may derive from task metadata sharing names with
    # kernel-loop variables; only the stored value must be exact
    code = """
        def flush(cache, group, payload):
            out, auxs, approximate = _kernel_batch(group)
            for t, o in zip(group, out):
                pass
            for t in group:
                cache.put(t.key, payload, 1)
    """
    assert check(tmp_path, code) == []


def test_hyg002_direct_warn_fires_helper_ok(tmp_path):
    bad = """
        import warnings
        def old_api():
            warnings.warn("old_api is deprecated", DeprecationWarning,
                          stacklevel=2)
    """
    assert "HYG002" in check(tmp_path, bad)
    good = """
        import warnings
        def _warn_old_api():
            warnings.warn("old_api is deprecated", DeprecationWarning,
                          stacklevel=3)
        def old_api():
            _warn_old_api()
    """
    assert check(tmp_path, good) == []


def test_hyg002_helper_without_stacklevel_fires(tmp_path):
    code = """
        import warnings
        def _warn_old_api():
            warnings.warn("old_api is deprecated", DeprecationWarning)
    """
    assert "HYG002" in check(tmp_path, code)


# --------------------------------------------------------- suppressions ---


def test_reasoned_suppression_silences(tmp_path):
    code = """
        import time
        def stamp():
            # detcheck: allow[DET001] telemetry-only, never merged
            return time.time()
    """
    assert check(tmp_path, code) == []


def test_suppression_without_reason_is_sup001(tmp_path):
    code = """
        import time
        def stamp():
            # detcheck: allow[DET001]
            return time.time()
    """
    assert check(tmp_path, code) == ["SUP001"]


def test_stale_suppression_is_sup002(tmp_path):
    code = """
        def stamp():
            # detcheck: allow[DET001] leftover from a removed clock
            return 42
    """
    assert check(tmp_path, code) == ["SUP002"]


def test_suppression_covers_only_its_own_and_next_line(tmp_path):
    code = """
        import time
        # detcheck: allow[DET001] comment two lines up covers nothing
        def stamp():
            return time.time()
    """
    fired = check(tmp_path, code)
    assert "DET001" in fired and "SUP002" in fired


# ---------------------------------------------------- tier + manifest -----


def test_per_file_tier_override_demotes(tmp_path):
    code = """
        # detcheck: tier=environment replays wall-clock traces by design
        import time
        def stamp():
            return time.time()
    """
    assert check(tmp_path, code) == []


def test_man001_fires_on_undeclared_package(tmp_path):
    pkg = tmp_path / "src" / "repro" / "newpkg"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    report = run([pkg], root=tmp_path)
    assert [v.rule for v in report.violations] == ["MAN001"]
    (pkg / "__init__.py").write_text('DETCHECK_TIER = "environment"\n')
    report = run([pkg], root=tmp_path)
    assert report.ok


# ------------------------------------------------------------------ CLI ---


def test_cli_list_rules_and_json_report(tmp_path, capsys):
    assert cli.main(["--list-rules"]) == 0
    assert "DET005" in capsys.readouterr().out

    bad = tmp_path / "bad.py"
    bad.write_text("import time\nt0 = time.time()\n")
    out = tmp_path / "report.json"
    rc = cli.main([str(bad), "--root", str(tmp_path),
                   "--tier", "deterministic", "--json", str(out)])
    assert rc == 1
    payload = json.loads(out.read_text())
    assert payload["ok"] is False
    assert payload["violations"][0]["rule"] == "DET001"


# ------------------------------------------- real tree + seeded drift -----


def repo_copy(tmp_path):
    dst = tmp_path / "repo"
    shutil.copytree(ROOT / "src", dst / "src")
    shutil.copytree(ROOT / "docs", dst / "docs")
    for f in ROOT.glob("*.md"):
        shutil.copy(f, dst / f.name)
    return dst


def tree_rules(dst):
    report = run([dst / "src" / "repro"], root=dst)
    return [v.rule for v in report.violations]


def test_self_run_src_repro_is_clean():
    report = run([ROOT / "src" / "repro"], root=ROOT)
    assert report.ok, "\n".join(v.format() for v in report.violations)
    assert report.files_scanned > 50
    # every suppression in the tree carries a reason (SUP001 would have
    # fired otherwise) — assert the catalog is fully documented too
    assert set(RULES) >= {"DET001", "DET005", "REG001", "REG007",
                          "HYG001", "SUP002", "DOC002", "MAN001"}


def test_seeded_wire_registry_row_drop_fails(tmp_path):
    dst = repo_copy(tmp_path)
    wire = dst / "src/repro/net/wire.py"
    s = wire.read_text()
    assert "MSG_SYNC_DONE: SyncDone," in s
    wire.write_text(s.replace("MSG_SYNC_DONE: SyncDone,", "", 1))
    fired = tree_rules(dst)
    assert "REG001" in fired       # codec halves out of sync
    assert "REG002" in fired       # PROTOCOL.md row now undocumented


def test_seeded_protocol_doc_extra_row_fails(tmp_path):
    dst = repo_copy(tmp_path)
    proto = dst / "docs" / "PROTOCOL.md"
    proto.write_text(proto.read_text()
                     + "\n| 0x7F | `GhostMsg` | seeded drift |\n")
    assert "REG002" in tree_rules(dst)


def test_seeded_warn_helper_revert_fails(tmp_path):
    # reverting the determinism/hygiene fix that routed the trust shim's
    # deprecation warning through _warn_gated_resolve must fail the pass
    dst = repo_copy(tmp_path)
    tr = dst / "src/repro/core/trust.py"
    s = tr.read_text()
    assert "_warn_gated_resolve" in s
    tr.write_text(s.replace("_warn_gated_resolve", "warn_gated_resolve"))
    assert "HYG002" in tree_rules(dst)


def test_seeded_crashpoint_without_site_fails(tmp_path):
    dst = repo_copy(tmp_path)
    j = dst / "src/repro/core/journal.py"
    s = j.read_text()
    anchor = "\nRECORD_TYPES: Dict[int, str]"
    assert anchor in s
    j.write_text(s.replace(
        anchor,
        '\nCP_GHOST = CrashPoint._declare("ghost.never_injected", "x")\n'
        + anchor, 1))
    assert "REG006" in tree_rules(dst)


def test_seeded_strategy_schema_drift_fails(tmp_path):
    dst = repo_copy(tmp_path)
    cat = dst / "src/repro/strategies/catalog.py"
    s = cat.read_text()
    old = 'schema={"trim": (float, 0.2)'
    assert old in s
    cat.write_text(s.replace(
        old, 'schema={"bogus_knob": (float, 0.5), "trim": (float, 0.2)', 1))
    assert "REG007" in tree_rules(dst)


def test_seeded_analysis_catalog_drift_fails(tmp_path):
    dst = repo_copy(tmp_path)
    a = dst / "docs" / "ANALYSIS.md"
    s = a.read_text()
    # direction 1: documented tier disagrees with the registered rule
    a.write_text(s.replace("| `DET003` | deterministic |",
                           "| `DET003` | global |", 1))
    assert "DOC002" in tree_rules(dst)
    # direction 2: a documented rule that is not registered
    a.write_text(s.replace("| `DET003` |", "| `DET999` |", 1))
    fired = tree_rules(dst)
    assert "DOC002" in fired
