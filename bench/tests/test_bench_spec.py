"""The harness finds every piece of a cell by the names in BENCHMARK.json,
and a piece dropped in as a new file is found without an edit."""
import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
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


#: an architecture with a draw of its own, as a state-space layer needs
#: for its ``D`` (ones) and the like; fan-in 16 makes "proj" exact
DRAW_ARCH = """
import jax.numpy as jnp

KINDS = {"skip": lambda z, shape: jnp.ones(shape, z.dtype),
         "wide": lambda z, shape: 2.0 * z}


def leaf_specs(cfg):
    return {"D": ((4, 8), "skip"), "w": ((16, 8), "proj"),
            "x": ((8, 8), "wide")}
"""


def test_an_architecture_brings_its_own_draw(tmp_path):
    """A draw kind declared by the architecture's module alone is drawn,
    beside the generic kinds, from the leaf's own key (leaf ``i`` of the
    sorted names)."""
    import weights
    name = "StubDrawForCausalLM"
    with tiny.arch_module(tmp_path, name, DRAW_ARCH):
        w = weights.make_weights({"architectures": [name]}, 2**31 + 5)

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def z(i, shape):
        return jax.random.normal(jax.random.fold_in(
            weights.seed_key(2**31 + 5), i), shape, jnp.float32)
    want = {"D": np.ones((4, 8), np.float32),
            "w": z(1, (16, 8)) * 0.25, "x": 2.0 * z(2, (8, 8))}
    for k, v in want.items():
        assert w[k].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(w[k]),
                                      np.asarray(jnp.asarray(v, jnp.bfloat16)))


@pytest.mark.parametrize("source", [
    "KINDS = {'proj': lambda z, shape: z}\n",
    "SCOPES = ('attention',)\n",
    "SCOPES = ('unscoped',)\n"])
def test_an_architecture_may_not_redefine_a_generic_name(tmp_path, source):
    """A draw kind or a scope that every architecture has already is not
    the module's to declare."""
    import weights
    from arch import scopes
    name = "StubClashForCausalLM"
    cfg = {"architectures": [name]}
    read = scopes if source.startswith("SCOPES") else weights.draws
    with tiny.arch_module(tmp_path, name, source):
        with pytest.raises(ValueError, match=name):
            read(cfg)
