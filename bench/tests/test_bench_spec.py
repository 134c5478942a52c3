"""The harness finds every piece of a cell by the names in BENCHMARK.json,
and a piece dropped in as a new file is found without an edit."""
import json
import re

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
from spec import BENCH, REPO, load_cell, metric_reader, read_json

SPEC = read_json(REPO / "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = load_cell(cell)
    assert c.config["vocab_size"] > 0 and c.traffic["loop"] == "closed"
    e2e = [m["name"] for m in c.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(metric_reader(m["name"]).read)
        assert m["moves"] in e2e


def test_names_and_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group == "configs", entry["name"]))
    assert len(set(names)) == len(names)
    for c in SPEC["configs"]:
        assert c["file"].startswith(SPEC["paths"][0] + "/")
        assert not set(c) - {"name", "source", "file", "reduced", "why"}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_dropped_in_files_are_found(tmp_path):
    """A new configuration, traffic mix and metric reader, each a file of
    its own, make a new cell with no code touched."""
    bench = tmp_path / BENCH.name
    for sub in ("configs", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True)
    cfg = dict(read_json(BENCH / "configs" / "qwen1.5-0.5b.sme-v2.json"),
               num_hidden_layers=3)
    (bench / "configs" / "new-model.json").write_text(json.dumps(cfg))
    mix = dict(read_json(BENCH / "traffic" / "closed-decode.json"),
               clients=5)
    (bench / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "new_metric.x.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec = {
        "command": SPEC["command"], "paths": SPEC["paths"],
        "run_seconds": SPEC["run_seconds"],
        "configs": [{"name": "new-model", "source": "x",
                     "file": f"{BENCH.name}/configs/new-model.json",
                     "reduced": ["num_hidden_layers"]}],
        "workloads": [{"name": "new-cell", "config": "new-model",
                       "traffic": "new-mix", "chips": 1, "why": "x"}],
        "end_to_end": SPEC["end_to_end"][:1] + [
            m for m in SPEC["end_to_end"] if m["name"] == "setup_s"],
        "per_layer": [{"name": "new_metric.x", "unit": "%",
                       "better": "higher", "source": "device_trace",
                       "layer": "x", "moves": "output_tok_s"}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    c = load_cell("new-cell", root=tmp_path)
    assert c.config["num_hidden_layers"] == 3
    assert c.traffic["clients"] == 5
    assert [m["name"] for m in c.per_layer] == ["new_metric.x"]
    assert metric_reader("new_metric.x", bench).read(None) == 42.0
    with pytest.raises(SystemExit):
        load_cell("no-such-cell", root=tmp_path)


def test_unknown_architecture_names_the_file_to_add():
    from arch import for_config
    cfg = dict(read_json(BENCH / "configs" / "qwen1.5-0.5b.sme-v2.json"),
               architectures=["DeepseekV2ForCausalLM"])
    with pytest.raises(ValueError, match=re.escape(
            f"add {BENCH.name}/arch/DeepseekV2ForCausalLM.py")):
        for_config(cfg)
    with pytest.raises(ValueError, match="arch/"):
        for_config(dict(cfg, architectures=["../spec"]))


#: where an architecture may be named: its module, its reference, its
#: configuration files (and the tests)
ARCH_DIRS = ("arch", "reference", "configs", "tests")
QWEN_NAMES = re.compile(r"qwen|\b(?:[qkvo]_[wb]|gate_w|up_w|down_w)\b",
                        re.IGNORECASE)


def test_only_architecture_files_name_one():
    """A configuration of another architecture needs only new files: no
    other file of the benchmark knows the names of one."""
    found = [f"{path.relative_to(BENCH)}:{n}"
             for path in sorted(BENCH.rglob("*")) if path.is_file()
             and path.relative_to(BENCH).parts[0] not in ARCH_DIRS
             and not path.relative_to(BENCH).parts[0].startswith(".")
             and path.suffix in (".py", ".json", ".jsonl", ".toml", ".txt",
                                 ".csv")
             for n, line in enumerate(path.read_text().splitlines(), 1)
             if QWEN_NAMES.search(line)]
    assert not found, found
