"""``BENCHMARK.json`` against the rules a benchmark manifest keeps, and every file it names."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
BENCH = ROOT / "benchmarks" / "chip"

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_size():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", *KEYS}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_command_and_paths():
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert PATH.fullmatch(p) and not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(one_line(w) for w in cmd)
    for word in cmd[1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in MANIFEST["paths"]), word
            assert (ROOT / word).is_file()


@pytest.mark.parametrize("kind", sorted(KEYS))
def test_entries_have_just_their_keys(kind):
    for entry in MANIFEST[kind]:
        extra = {"workloads"} if kind in ("end_to_end", "per_layer") else set()
        assert KEYS[kind] <= set(entry) <= KEYS[kind] | extra, entry["name"]


def test_names_and_units_use_the_allowed_characters():
    for kind in KEYS:
        for entry in MANIFEST[kind]:
            assert NAME.fullmatch(entry["name"]), entry["name"]
            if "unit" in entry:
                assert UNIT.fullmatch(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
                assert entry["source"] in SOURCES
            for key in ("why", "layer"):
                if key in entry:
                    assert one_line(entry[key]), (entry["name"], key)
            if kind == "configs":
                assert one_line(entry["source"]), entry["name"]
    for cell in MANIFEST["workloads"]:
        assert NAME.fullmatch(cell["config"]) and NAME.fullmatch(cell["traffic"])
    for cfg in MANIFEST["configs"]:
        assert len(cfg["reduced"]) <= 16 and all(NAME.fullmatch(k) for k in cfg["reduced"])
    metric_names = [m["name"] for k in ("end_to_end", "per_layer") for m in MANIFEST[k]]
    assert len(set(metric_names)) == len(metric_names)
    for kind in ("configs", "workloads"):
        got = [e["name"] for e in MANIFEST[kind]]
        assert len(set(got)) == len(got)


def test_files_under_paths_are_named_from_name_characters():
    for p in MANIFEST["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts or any(part.startswith(".") and part != ".gitignore"
                                               for part in f.relative_to(ROOT).parts):
                continue
            assert PATH.fullmatch(str(f.relative_to(ROOT))), f


def test_every_configuration_has_a_cell_and_its_files():
    used = {c["config"] for c in MANIFEST["workloads"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(set(files)) == len(files)
    for cfg in MANIFEST["configs"]:
        assert cfg["name"] in used
        assert any(cfg["file"].startswith(p + "/") for p in MANIFEST["paths"])
        body = json.loads((ROOT / cfg["file"]).read_text())
        assert body["name"] == cfg["name"]
        assert body["source"] == cfg["source"]
        assert sorted(body["reduced"]) == sorted(cfg["reduced"])
        assert set(body["reduced"]) <= set(body["published"])
        assert body["assumed"] and body["dtype"] == "float32"
        assert (BENCH / "generators" / f"{body['generator']}.py").is_file()
        assert body["check"]["max_err"] > 0


def test_cells():
    pairs = [(c["config"], c["traffic"]) for c in MANIFEST["workloads"]]
    assert 1 <= len(pairs) <= 24 and len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in MANIFEST["configs"]}
    for cell in MANIFEST["workloads"]:
        assert cell["config"] in configs
        assert cell["chips"] in (1, 4)
        mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
        assert (BENCH / "loops" / f"{mix['loop']}.py").is_file()


def test_at_most_half_the_cells_on_four_chips():
    cells = MANIFEST["workloads"]
    four = sum(c["chips"] == 4 for c in cells)
    assert four <= max(1, len(cells) // 2)


def test_end_to_end_metrics_and_bounds():
    e2e = MANIFEST["end_to_end"]
    assert 1 <= len(e2e) <= 16
    for m in e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in e2e)
    for cell in MANIFEST["workloads"]:
        got = [m["name"] for m in e2e if reports(m, cell["name"])]
        assert "setup_s" in got and len(got) >= 2, cell["name"]
        assert any(reports(m, cell["name"]) for m in MANIFEST["per_layer"]), cell["name"]


def test_every_per_layer_metric_moves_what_each_of_its_cells_reports():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    cells = {c["name"] for c in MANIFEST["workloads"]}
    assert 1 <= len(MANIFEST["per_layer"]) <= 128
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", sorted(cells)):
            assert cell in cells
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_metric_has_a_reader():
    for kind in ("end_to_end", "per_layer"):
        for m in MANIFEST[kind]:
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = MANIFEST["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
