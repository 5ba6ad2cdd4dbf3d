"""One harness, one place for a speed figure (PR 29).

``benchmarks/`` (``BENCHMARK.json``) is the only harness and ``PERF.md`` +
``PERF_LEDGER.jsonl`` the only place a speed figure may stand. The pre-chip
harness is gone; nothing that is left may justify itself by it, and README's
``## Performance`` names exactly what ``BENCHMARK.json`` declares.

``ROADMAP.md``, ``PERF.md``, ``CHANGES.md``, ``SURVEY.md``, ``ADVICE.md``,
``ISSUE.md`` and ``benchmarks/`` are outside the scan: history, and files
other sessions rewrite.
"""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
GONE = re.compile(r"bench\.py|bench ladder|ladder #[0-9]", re.IGNORECASE)

CLASSES = {
    "program": ["kubernetes_tpu"],
    "tests": ["tests"],
    "scripts_and_smoke": ["scripts", "chip_smoke.py", "docs"],
    "documents": ["README.md", "BASELINE.md"],
}


def _files(entry):
    path = ROOT / entry
    if path.is_file():
        return [path]
    return [
        p for p in path.rglob("*")
        if p.is_file() and "__pycache__" not in p.parts
    ]


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_nothing_answers_to_the_removed_harness(cls):
    paths = [
        p for entry in CLASSES[cls] for p in _files(entry)
        if p != Path(__file__).resolve()
    ]
    assert paths, f"class {cls} matched no file"
    hits = [
        f"{p.relative_to(ROOT)}:{n}: {line.strip()[:100]}"
        for p in paths
        for n, line in enumerate(
            p.read_text(encoding="utf-8", errors="replace").splitlines(), 1
        )
        if GONE.search(line)
    ]
    assert not hits, "\n".join(hits)


def _performance_section():
    text = (ROOT / "README.md").read_text(encoding="utf-8")
    m = re.search(r"^## Performance\n(.*?)(?=^## )", text, re.M | re.S)
    assert m, "README has no `## Performance` section"
    return m.group(1)


def test_readme_performance_names_what_the_benchmark_declares():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text(encoding="utf-8"))
    cells = {w["name"] for w in bench["workloads"]}
    metrics = {m["name"] for m in bench["end_to_end"]}
    section = _performance_section()
    quoted = set(re.findall(r"`([^`\n]+)`", section))

    missing = sorted((cells | metrics) - quoted)
    assert not missing, f"README `## Performance` does not name {missing}"

    # a back-quoted <name>.<traffic> (lower-case, hyphenated, one dot, no
    # file suffix) is a cell; *_per_s and setup_s are end-to-end metrics
    suffixes = {"py", "md", "json", "jsonl", "yaml", "yml", "sh", "txt"}
    cell_like = {
        q for q in quoted
        if re.fullmatch(r"[a-z0-9]+(?:-[a-z0-9]+)+\.[a-z]+", q)
        and q.rsplit(".", 1)[1] not in suffixes
    }
    metric_like = {
        q for q in quoted if re.fullmatch(r"[a-z0-9_]+_per_s|setup_s", q)
    }
    unknown = sorted((cell_like - cells) | (metric_like - metrics))
    assert not unknown, f"not in BENCHMARK.json: {unknown}"
    assert "benchmarks/run.py" in section
