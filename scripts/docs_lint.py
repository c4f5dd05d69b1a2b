"""Docs lint: the documentation suite exists, is substantive, every repo
path it references resolves, and every backtick-quoted ``module.symbol``
code reference resolves via import/getattr — so docs cannot silently rot
when code moves or is renamed.

  PYTHONPATH=src python scripts/docs_lint.py      (or: make docs-lint)

Checks:
  * README.md, docs/ARCHITECTURE.md, docs/API.md, docs/BENCHMARKS.md and
    docs/HINTS.md exist and are non-trivial;
  * every `path`-looking backtick reference into src/ tests/ benchmarks/
    examples/ docs/ scripts/ points at a real file or directory;
  * every dotted backtick reference anchored in this repo's code — a
    module (`workload.lower_trace`, `cluster_sim.SimParams`), a
    `repro.core` export (`Namenode.execute_batch`, `OpSpec.lease_order`),
    or a symbol of any `repro.core` submodule (`BatchedHopsFSSim`) —
    resolves to a live object. Dotted tokens anchored NOWHERE in the repo
    (example variables like `dfs.batch`, version numbers) are prose, not
    code references, and are skipped;
  * the top-level keys documented in docs/BENCHMARKS.md's "Output schema"
    block match the actual top-level keys of BENCH_throughput.json, both
    directions — the benchmark artifact and its documentation cannot
    drift apart silently;
  * the artifact's `failover` section (§7.6 kill-a-namenode-mid-replay
    measurement) carries the full metric set the chaos suite and docs
    rely on (dip depth, recovery time/ops, zero-bin count, fault events);
  * the artifact's `elasticity` section (scale-the-fleet-mid-replay
    measurement, docs/ELASTICITY.md) carries the full metric set the
    elastic-pool suite and docs rely on (scale-out gain, zero-bin count,
    hint hit rates around migration, oracle equality, scale events);
  * the artifact's `overload` section (gray-failure goodput measurement,
    docs/ROBUSTNESS.md) carries the full metric set the admission suite
    and docs rely on (protected vs unprotected goodput and p99, breaker
    trips, admission telemetry, oracle equality) and its headline
    acceptance criteria hold: zero protected late completions, protected
    goodput strictly above unprotected;
  * the artifact's `columnar` section (struct-of-arrays engine
    differential bench, docs/ARCHITECTURE.md's columnar-engine section)
    carries the full metric set, shows the oracle lock holding
    (`state_matches_oracle` true) and genuinely fused kernel launches
    (launch count strictly below op count);
  * the artifact's `big_dir` section (§6 subtree protocol at 10^5-inode
    scale, docs/ARCHITECTURE.md's million-entry-directories section)
    carries the full metric set and its acceptance criteria hold:
    adjacent-op p99 within 3x of the no-subtree baseline, dict/columnar
    and incremental/legacy state equality, treeagg launches with zero
    fallback demotions, and a genuinely paced delete.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import pkgutil
import re
import sys
import types
from pathlib import Path
from typing import Dict, Optional

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))            # benchmarks/, scripts/
sys.path.insert(0, str(ROOT / "src"))    # repro

DOCS = ["README.md", "docs/ARCHITECTURE.md", "docs/API.md",
        "docs/BENCHMARKS.md", "docs/CHAOS.md", "docs/ELASTICITY.md",
        "docs/HINTS.md", "docs/ROBUSTNESS.md"]
MIN_BYTES = 1500
REF_PREFIXES = ("src/", "tests/", "benchmarks/", "examples/", "docs/",
                "scripts/")
#: module-name prefixes tried in front of a dotted token; the bare base
#: only applies to the repo's own top-level packages (REPO_ROOTS) — a
#: stdlib/site-packages module must never anchor a doc token, else prose
#: like `pytest.something` would fail the lint and a dangling `re.py`
#: would pass it
IMPORT_BASES = ("", "repro.", "repro.core.", "repro.kernels.",
                "benchmarks.")
REPO_ROOTS = ("repro", "benchmarks", "scripts", "examples", "tests")

# `...`-quoted tokens that look like repo paths or dotted symbols
_REF = re.compile(r"`([A-Za-z0-9_./-]+)`")

_symbol_cache: Dict[str, Optional[bool]] = {}


def _import(name: str):
    try:
        return importlib.import_module(name)
    except Exception:
        return None


def _walk_attrs(obj, parts) -> bool:
    for i, attr in enumerate(parts):
        try:
            obj = getattr(obj, attr)
        except AttributeError:
            # dataclass fields without defaults are not class attributes —
            # accept one only as the FINAL part (nothing to walk past it)
            return (i == len(parts) - 1
                    and dataclasses.is_dataclass(obj)
                    and any(f.name == attr
                            for f in dataclasses.fields(obj)))
    return True


def _submodule_roots():
    """name -> object for every public symbol of every repro.core
    submodule (anchors refs like `BatchedHopsFSSim.batched_ops` that are
    not re-exported from the package root). Imported FOREIGN modules
    (``import time`` inside a submodule) are excluded — the stdlib must
    not anchor doc tokens."""
    roots: Dict[str, object] = {}

    def repo_owned(obj) -> bool:
        if isinstance(obj, types.ModuleType):
            return getattr(obj, "__name__", "").startswith(
                ("repro", "benchmarks"))
        return True

    core = _import("repro.core")
    if core is None:
        return roots
    for info in pkgutil.iter_modules(core.__path__):
        mod = _import(f"repro.core.{info.name}")
        if mod is None:
            continue
        for name in dir(mod):
            obj = getattr(mod, name)
            if not name.startswith("__") and repo_owned(obj):
                roots.setdefault(name, obj)
    for name in dir(core):
        obj = getattr(core, name)
        if not name.startswith("__") and repo_owned(obj):
            roots[name] = obj
    return roots


_ATTR_ROOTS = None


def symbol_status(tok: str) -> Optional[bool]:
    """True = resolves, False = anchored in repo code but dangling,
    None = not a code reference (skip)."""
    global _ATTR_ROOTS
    if tok in _symbol_cache:
        return _symbol_cache[tok]
    parts = tok.split(".")
    first = parts[0]
    anchored = resolved = False
    for base in IMPORT_BASES:
        if base == "" and first not in REPO_ROOTS:
            continue
        if _import(base + first) is None:
            continue
        anchored = True
        # longest importable module prefix, then getattr the rest
        for k in range(len(parts), 0, -1):
            mod = _import(base + ".".join(parts[:k]))
            if mod is None:
                continue
            if _walk_attrs(mod, parts[k:]):
                resolved = True
            break
        if resolved:
            break
    if not resolved:
        if _ATTR_ROOTS is None:
            _ATTR_ROOTS = _submodule_roots()
        if first in _ATTR_ROOTS:
            anchored = True
            resolved = _walk_attrs(_ATTR_ROOTS[first], parts[1:])
    status = True if resolved else (False if anchored else None)
    _symbol_cache[tok] = status
    return status


def check_doc(path: Path) -> list:
    errors = []
    if not path.exists():
        return [f"{path.relative_to(ROOT)}: missing"]
    text = path.read_text()
    if len(text) < MIN_BYTES:
        errors.append(f"{path.relative_to(ROOT)}: suspiciously short "
                      f"({len(text)} bytes < {MIN_BYTES})")
    for tok in _REF.findall(text):
        if "/" in tok or tok.startswith(REF_PREFIXES):
            if not tok.startswith(REF_PREFIXES):
                continue
            target = ROOT / tok
            # allow references to glob-ish groups like src/repro/kernels/
            if target.exists():
                continue
            # `a/{b,c}/d` brace groups: every expansion must exist
            m = re.match(r"(.*)\{([^}]+)\}(.*)", tok)
            if m and all((ROOT / (m.group(1) + part + m.group(3))).exists()
                         for part in m.group(2).split(",")):
                continue
            errors.append(f"{path.relative_to(ROOT)}: dangling reference "
                          f"`{tok}`")
            continue
        if tok.endswith(".py"):
            # bare module filename (`ops_registry.py`): the file must
            # exist somewhere under the repo's code trees (repo-anchored
            # imports only — the stdlib must not vouch for `re.py`)
            stem = tok[:-3]
            if any(_import(base + stem) for base in IMPORT_BASES
                   if base != "" or stem in REPO_ROOTS) \
                    or (ROOT / tok).exists() \
                    or list(ROOT.glob(f"*/{tok}")) \
                    or list(ROOT.glob(f"*/**/{tok}")):
                continue
            errors.append(f"{path.relative_to(ROOT)}: dangling module "
                          f"reference `{tok}`")
            continue
        if "." in tok and not tok[0].isdigit():
            if symbol_status(tok) is False:
                errors.append(f"{path.relative_to(ROOT)}: dangling code "
                              f"reference `{tok}` (anchored in repo code "
                              f"but does not resolve via import/getattr)")
    return errors


#: top-level key lines of the jsonc schema block: exactly two spaces of
#: indent, a quoted identifier, a colon
_SCHEMA_KEY = re.compile(r'^  "([A-Za-z_][A-Za-z0-9_]*)":', re.M)


def check_benchmarks_schema(doc: Path, artifact: Path) -> list:
    """Cross-check the documented `BENCH_throughput.json` top-level schema
    against the committed artifact: every documented key must exist in the
    artifact, and every artifact key must be documented."""
    if not doc.exists():
        return []                      # the missing doc is reported above
    if not artifact.exists():
        return [f"{artifact.name}: missing (docs/BENCHMARKS.md documents "
                f"its schema; regenerate with `make bench`)"]
    text = doc.read_text()
    m = re.search(r"```jsonc\n(.*?)```", text, re.S)
    if m is None:
        return [f"{doc.relative_to(ROOT)}: no ```jsonc schema block to "
                f"cross-check against {artifact.name}"]
    documented = set(_SCHEMA_KEY.findall(m.group(1)))
    try:
        actual = set(json.loads(artifact.read_text()))
    except Exception as e:
        return [f"{artifact.name}: unparseable ({e})"]
    errors = []
    for k in sorted(documented - actual):
        errors.append(f"{doc.relative_to(ROOT)}: documents top-level key "
                      f"`{k}` absent from {artifact.name}")
    for k in sorted(actual - documented):
        errors.append(f"{artifact.name}: top-level key `{k}` undocumented "
                      f"in {doc.relative_to(ROOT)}'s schema block")
    return errors


#: metric keys the `failover` section of BENCH_throughput.json must carry
#: (consumed by docs/CHAOS.md and the chaos suite's bench cross-checks)
FAILOVER_KEYS = frozenset({
    "n_namenodes", "killed_namenode", "kill_at_s", "restart_at_s",
    "horizon_s", "timeline_bin_s", "steady_ops_per_bin",
    "dip_ops_per_bin", "dip_depth_pct", "recovered", "recovery_s",
    "ops_to_recovery", "zero_bins_after_kill", "requeued_ops",
    "completed_ops", "fault_events",
})


def check_failover_schema(artifact: Path) -> list:
    """The bench artifact's §7.6 failover section must exist and carry
    every documented metric key."""
    if not artifact.exists():
        return []                 # already reported by the schema check
    try:
        report = json.loads(artifact.read_text())
    except Exception:
        return []                 # already reported by the schema check
    fo = report.get("failover")
    if not isinstance(fo, dict):
        return [f"{artifact.name}: no `failover` section (regenerate "
                f"with `make bench`)"]
    errors = []
    for k in sorted(FAILOVER_KEYS - set(fo)):
        errors.append(f"{artifact.name}: failover section missing "
                      f"metric `{k}`")
    ev = fo.get("fault_events")
    if not ev:
        errors.append(f"{artifact.name}: failover section recorded no "
                      f"fault events — no namenode was killed")
    return errors


#: metric keys the `elasticity` section of BENCH_throughput.json must
#: carry (consumed by docs/ELASTICITY.md and the elastic-pool suite)
ELASTICITY_KEYS = frozenset({
    "n_namenodes_base", "n_namenodes_peak", "scale_out_at_s",
    "scale_in_at_s", "horizon_s", "timeline_bin_s", "steady_ops_per_bin",
    "scaled_ops_per_bin", "scale_out_gain_pct",
    "zero_bins_during_scale_out", "scale_in_recovered",
    "scale_in_recovery_s", "completed_ops", "scale_events",
    "hint_hit_rate_before", "hint_hit_rate_after",
    "hint_hit_rate_drop_pct", "migrated_hint_entries",
    "pool_scale_outs", "pool_scale_ins", "state_matches_sequential",
})


def check_elasticity_schema(artifact: Path) -> list:
    """The bench artifact's elastic-pool section must exist and carry
    every documented metric key."""
    if not artifact.exists():
        return []                 # already reported by the schema check
    try:
        report = json.loads(artifact.read_text())
    except Exception:
        return []                 # already reported by the schema check
    el = report.get("elasticity")
    if not isinstance(el, dict):
        return [f"{artifact.name}: no `elasticity` section (regenerate "
                f"with `make bench`)"]
    errors = []
    for k in sorted(ELASTICITY_KEYS - set(el)):
        errors.append(f"{artifact.name}: elasticity section missing "
                      f"metric `{k}`")
    ev = el.get("scale_events")
    if not ev:
        errors.append(f"{artifact.name}: elasticity section recorded no "
                      f"scale events — the fleet never resized")
    return errors


#: metric keys the `overload` section of BENCH_throughput.json must
#: carry (consumed by docs/ROBUSTNESS.md and the admission suite)
OVERLOAD_KEYS = frozenset({
    "n_namenodes", "slow_namenode", "delay_ticks_per_exchange", "n_ops",
    "n_tenants", "zipf_s", "batch_size", "deadline_budget_ticks",
    "deadline_per_op_ticks", "unprotected", "protected",
    "goodput_gain_pct", "planner_deadline_shed",
    "planner_breaker_rerouted", "breaker_trips", "breaker_open_at_end",
    "admission", "recovery_redriven_ops", "state_matches_sequential",
})

#: per-run metric keys of the `unprotected` / `protected` sub-sections
OVERLOAD_RUN_KEYS = frozenset({
    "ok", "goodput_ops", "goodput_frac", "late_completions",
    "failed_by_error", "per_tenant_p99_ticks", "worst_tenant_p99_ticks",
    "clock_advance_ticks",
})


def check_overload_schema(artifact: Path) -> list:
    """The bench artifact's gray-failure overload section must exist,
    carry every documented metric key, and satisfy the acceptance
    criteria the robustness layer is sold on."""
    if not artifact.exists():
        return []                 # already reported by the schema check
    try:
        report = json.loads(artifact.read_text())
    except Exception:
        return []                 # already reported by the schema check
    ov = report.get("overload")
    if not isinstance(ov, dict):
        return [f"{artifact.name}: no `overload` section (regenerate "
                f"with `make bench`)"]
    errors = []
    for k in sorted(OVERLOAD_KEYS - set(ov)):
        errors.append(f"{artifact.name}: overload section missing "
                      f"metric `{k}`")
    for run in ("unprotected", "protected"):
        sub = ov.get(run)
        if not isinstance(sub, dict):
            continue              # missing-key error already emitted
        for k in sorted(OVERLOAD_RUN_KEYS - set(sub)):
            errors.append(f"{artifact.name}: overload.{run} missing "
                          f"metric `{k}`")
    u, p = ov.get("unprotected"), ov.get("protected")
    if isinstance(u, dict) and isinstance(p, dict):
        if p.get("late_completions") != 0:
            errors.append(f"{artifact.name}: overload.protected completed "
                          f"{p.get('late_completions')} ops past their "
                          f"deadline — deadline shedding is not airtight")
        if not (p.get("goodput_frac", 0) > u.get("goodput_frac", 1)):
            errors.append(f"{artifact.name}: overload protection did not "
                          f"beat the unprotected run on goodput "
                          f"({p.get('goodput_frac')} <= "
                          f"{u.get('goodput_frac')})")
    if not ov.get("breaker_trips"):
        errors.append(f"{artifact.name}: overload section recorded no "
                      f"breaker trips — the slow namenode was never "
                      f"quarantined")
    if ov.get("state_matches_sequential") is not True:
        errors.append(f"{artifact.name}: overload recovery did not "
                      f"converge on the sequential oracle's namespace")
    return errors


#: metric keys the `columnar` section of BENCH_throughput.json must carry
#: (consumed by docs/ARCHITECTURE.md's columnar-engine section and the
#: differential suite in tests/test_columnar_store.py)
COLUMNAR_KEYS = frozenset({
    "batch_size", "window", "n_namenodes", "ops", "modes",
    "hintchain_launches", "pkval_launches", "pkval_probes",
    "pkval_demotions", "fused_launches", "launches_per_op",
    "wall_s_dict", "wall_s_columnar", "state_matches_oracle",
})

#: per-mode metric keys of the `modes.spotify` / `modes.write_heavy`
#: sub-sections
COLUMNAR_MODE_KEYS = frozenset({
    "ops", "ok", "failed", "windows", "hintchain_launches",
    "pkval_launches", "pkval_probes", "pkval_demotions",
    "window_ms_dict", "window_ms_columnar", "state_matches_oracle",
})


def check_columnar_schema(artifact: Path) -> list:
    """The bench artifact's columnar-engine section must exist, carry
    every documented metric key, and satisfy the oracle lock the engine
    is sold on: byte-identical final state and FUSED kernel launches
    (launch count orders of magnitude below op count)."""
    if not artifact.exists():
        return []                 # already reported by the schema check
    try:
        report = json.loads(artifact.read_text())
    except Exception:
        return []                 # already reported by the schema check
    co = report.get("columnar")
    if not isinstance(co, dict):
        return [f"{artifact.name}: no `columnar` section (regenerate "
                f"with `make bench`)"]
    errors = []
    for k in sorted(COLUMNAR_KEYS - set(co)):
        errors.append(f"{artifact.name}: columnar section missing "
                      f"metric `{k}`")
    for mode, sub in (co.get("modes") or {}).items():
        if not isinstance(sub, dict):
            errors.append(f"{artifact.name}: columnar.modes.{mode} is "
                          f"not a metrics object")
            continue
        for k in sorted(COLUMNAR_MODE_KEYS - set(sub)):
            errors.append(f"{artifact.name}: columnar.modes.{mode} "
                          f"missing metric `{k}`")
    if co.get("state_matches_oracle") is not True:
        errors.append(f"{artifact.name}: columnar replay diverged from "
                      f"the dict-store oracle (state_matches_oracle "
                      f"!= true)")
    if not co.get("fused_launches"):
        errors.append(f"{artifact.name}: columnar section recorded no "
                      f"fused kernel launches — the gates never opened")
    elif not co.get("fused_launches", 0) < co.get("ops", 0):
        errors.append(f"{artifact.name}: columnar launches "
                      f"({co.get('fused_launches')}) not below op count "
                      f"({co.get('ops')}) — batching is not fused")
    return errors


#: metric keys the `big_dir` section of BENCH_throughput.json must carry
#: (consumed by docs/ARCHITECTURE.md's million-entry-directories section
#: and the subtree suites in tests/test_subtree_properties.py /
#: tests/test_subtree_scaling.py)
BIG_DIR_KEYS = frozenset({
    "n_children", "total_inodes", "batch_size", "deleted", "chunks",
    "waves", "peak_frontier", "subtree_wall_s_dict",
    "subtree_wall_s_columnar", "adjacent_ops", "pace_invocations",
    "baseline_p50_ms", "baseline_p99_ms", "paced_p50_ms", "paced_p99_ms",
    "p99_ratio", "treeagg_launches", "treeagg_demotions",
    "state_matches_oracle", "incremental_matches_legacy",
})

#: adjacent-op p99 while the paced delete runs may be at most this
#: multiple of the no-subtree baseline (the "namespace stays live" bar)
BIG_DIR_MAX_P99_RATIO = 3.0


def check_big_dir_schema(artifact: Path) -> list:
    """The bench artifact's big-directory section must exist, carry
    every documented metric key, and satisfy the §6-at-scale acceptance
    criteria: adjacent-op p99 within 3x of the no-subtree baseline,
    both equality flags true, and the treeagg kernel gate genuinely
    opened (launches >= 1 with zero fallback demotions)."""
    if not artifact.exists():
        return []                 # already reported by the schema check
    try:
        report = json.loads(artifact.read_text())
    except Exception:
        return []                 # already reported by the schema check
    bd = report.get("big_dir")
    if not isinstance(bd, dict):
        return [f"{artifact.name}: no `big_dir` section (regenerate "
                f"with `make bench`)"]
    errors = []
    for k in sorted(BIG_DIR_KEYS - set(bd)):
        errors.append(f"{artifact.name}: big_dir section missing "
                      f"metric `{k}`")
    ratio = bd.get("p99_ratio")
    if isinstance(ratio, (int, float)) \
            and ratio > BIG_DIR_MAX_P99_RATIO:
        errors.append(f"{artifact.name}: adjacent-op p99 degraded "
                      f"{ratio}x during the paced delete (bar: "
                      f"{BIG_DIR_MAX_P99_RATIO}x over the no-subtree "
                      f"baseline)")
    if bd.get("state_matches_oracle") is not True:
        errors.append(f"{artifact.name}: big_dir replay diverged "
                      f"between the dict and columnar backends "
                      f"(state_matches_oracle != true)")
    if bd.get("incremental_matches_legacy") is not True:
        errors.append(f"{artifact.name}: incremental subtree engine "
                      f"diverged from the legacy engine "
                      f"(incremental_matches_legacy != true)")
    if not bd.get("treeagg_launches"):
        errors.append(f"{artifact.name}: big_dir section recorded no "
                      f"treeagg launches — the kernel gate never opened")
    if bd.get("treeagg_demotions"):
        errors.append(f"{artifact.name}: big_dir run demoted "
                      f"{bd.get('treeagg_demotions')} treeagg launches "
                      f"to the fallback — the kernel is not healthy")
    if not bd.get("pace_invocations"):
        errors.append(f"{artifact.name}: big_dir delete never paced — "
                      f"no adjacent ops interleaved between chunks")
    return errors


def main() -> int:
    errors = []
    for rel in DOCS:
        errors.extend(check_doc(ROOT / rel))
    errors.extend(check_benchmarks_schema(ROOT / "docs/BENCHMARKS.md",
                                          ROOT / "BENCH_throughput.json"))
    errors.extend(check_failover_schema(ROOT / "BENCH_throughput.json"))
    errors.extend(check_elasticity_schema(ROOT / "BENCH_throughput.json"))
    errors.extend(check_overload_schema(ROOT / "BENCH_throughput.json"))
    errors.extend(check_columnar_schema(ROOT / "BENCH_throughput.json"))
    errors.extend(check_big_dir_schema(ROOT / "BENCH_throughput.json"))
    if errors:
        print("docs-lint: FAIL")
        for e in errors:
            print("  -", e)
        return 1
    print(f"docs-lint: OK ({len(DOCS)} docs checked, "
          f"{len(_symbol_cache)} code references resolved)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
