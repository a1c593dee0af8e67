"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as files and entries, without editing a file that is there.

The files are `tests/data/added_cell/`: the 2 kb configuration, the
four-chip traffic mix of PERF.md's Open questions (`--devices 4`) and one
more reader.  The test builds a checkout as the driver would see it after
such a PR (the benchmark copied, the new files dropped beside the old, the
entries appended to BENCHMARK.json) and rehearses the new cell there on
four forced host devices: a check of control flow, on the CPU, no speed.
"""

import json
import os
import shutil
import subprocess
import sys

from harness import manifest

ADDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "added_cell")
NOT_LINKED = {"BENCHMARK.json", "benchmark", ".bench_work", ".git", "chiprun_out"}


def checkout_with_the_added_cell(root: str) -> str:
    for name in os.listdir(manifest.ROOT):
        if name not in NOT_LINKED:
            os.symlink(os.path.join(manifest.ROOT, name), os.path.join(root, name))
    shutil.copytree(manifest.HERE, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for kind in ("configs", "traffic", "metrics"):
        for f in os.listdir(os.path.join(ADDED, kind)):
            target = os.path.join(root, "benchmark", kind, f)
            assert not os.path.exists(target), f"{kind}/{f} would replace a file"
            shutil.copy(os.path.join(ADDED, kind, f), target)
    doc = manifest.load()
    with open(os.path.join(ADDED, "entries.json")) as f:
        added = json.load(f)
    cell = added["workloads"][0]["name"]
    for m in doc["per_layer"]:           # the new cell reports the old metrics too
        m["workloads"].append(cell)
    for key, entries in added.items():
        doc[key] += entries
    manifest.validate(doc)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return cell


def test_a_cell_added_as_files_and_entries_is_rehearsed_on_four_devices(tmp_path):
    cell = checkout_with_the_added_cell(str(tmp_path))
    env = dict(os.environ, PBCCS_DEVICE_REFINE="0")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "5",
         "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=1500)
    lines = done.stdout.strip().splitlines()
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == 4
    assert last["metrics"] == {}               # a CPU run reports no metric
    reported = [ln for ln in lines if ln.startswith("rehearsal: the cell reports")]
    assert reported and "polish_dispatches" in reported[0] \
        and "refine_rounds_per_dispatch" in reported[0]
    assert any("--devices" in ln or "devices 4" in ln or '"count": 4' in ln for ln in lines)
