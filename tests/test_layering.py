"""The arrows between the packages of pbccs_tpu/ point down.

A unit is a package directory or a top-level module of pbccs_tpu/.  ORDER
lists them from the bottom up (docs/DESIGN.md, "Layers", draws it as
five boxes); a unit imports only units below it.  Imports are read from
the AST, function-level ones included, and no code of the program is
imported.  An arrow that points up is a debt with a name in ROADMAP.md
and an entry in ALLOWED; an entry whose arrow has gone fails its case, so
the list can only shrink.
"""

import ast
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent / "pbccs_tpu"

ORDER = (
    # foundation
    "utils", "obs", "runtime", "native",
    # kernels and models
    "align", "ops", "models", "poa", "parallel",
    # the host pipeline
    "resilience", "io", "simulate", "pipeline", "sched",
    # services
    "serve", "tune",
    # entry points
    "analysis", "cli", "contract",
)

# (importing file, unit it reaches up to) -> the ROADMAP.md debt
ALLOWED = {
    # D12: Failure / ConsensusResult / ResultTally live in pipeline.py
    ("io/report.py", "pipeline"): "D12",
    ("resilience/quarantine.py", "pipeline"): "D12",
    ("resilience/checkpoint.py", "pipeline"): "D12",
    # D13: obs/ reaches up (`ccs top` lives there; the ledger and the
    # tracer read the governor's and the logger's state)
    ("obs/console.py", "serve"): "D13",
    ("obs/ledger.py", "resilience"): "D13",
    ("obs/trace.py", "resilience"): "D13",
    ("obs/ledger.py", "runtime"): "D13",
    ("obs/profiling.py", "runtime"): "D13",
    # D14: the kernels' and the runtime's upward reads
    ("models/arrow/scorer.py", "parallel"): "D14",
    ("runtime/tuning.py", "tune"): "D14",
    ("runtime/chemistry.py", "io"): "D14",
    ("native.py", "poa"): "D14",
    ("ops/fwdbwd.py", "models"): "D14",
    ("ops/fwdbwd_pallas.py", "models"): "D14",
    ("ops/fwdbwd_ref.py", "models"): "D14",
    ("ops/dense_score_pallas.py", "models"): "D14",
    ("ops/mutation_score.py", "models"): "D14",
    # `ccs serve` / `ccs router` reuse the batch CLI's argument groups
    ("serve/server.py", "cli"): "serve -> cli",
}


def _unit_of(path: pathlib.Path) -> str:
    rel = path.relative_to(ROOT).parts
    return rel[0][:-3] if len(rel) == 1 else rel[0]


def _imported_units(path: pathlib.Path):
    """Units of pbccs_tpu/ that the file at `path` imports."""
    package = ("pbccs_tpu",) + path.relative_to(ROOT).parts[:-1]
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = ".".join(package[:len(package) - node.level + 1]
                            if node.level else ())
            mod = ".".join(p for p in (base, node.module) if p)
            names = ([f"{mod}.{a.name}" for a in node.names]
                     if mod == "pbccs_tpu" else [mod])
        else:
            continue
        for name in names:
            parts = name.split(".")
            if parts[0] == "pbccs_tpu" and len(parts) > 1 \
                    and parts[1] in ORDER:
                yield parts[1]


def _arrows_up(unit: str) -> set[tuple[str, str]]:
    rank = ORDER.index(unit)
    top = ROOT / f"{unit}.py"
    files = [top] if top.exists() else sorted((ROOT / unit).rglob("*.py"))
    return {(str(f.relative_to(ROOT)), to)
            for f in files for to in _imported_units(f)
            if ORDER.index(to) > rank}


def test_order_names_every_unit():
    found = {_unit_of(p) for p in ROOT.rglob("*.py")} - {"__init__"}
    assert found == set(ORDER)


@pytest.mark.parametrize("unit", ORDER)
def test_unit_imports_only_units_below_it(unit):
    up = _arrows_up(unit)
    allowed = {k for k in ALLOWED if _unit_of(ROOT / k[0]) == unit}
    assert up - allowed == set(), "an arrow that points up, with no debt"
    assert allowed - up == set(), "a debt that is paid: drop its entry"
