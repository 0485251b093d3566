"""BENCHMARK.json and the files it names: every configuration, traffic mix,
per-layer metric and cell limit loads by name, the entries keep to the
benchmark's format, and the Qwen3 configuration is the program's registered
one except for the keys it lists as reduced."""
import json
import re
from pathlib import Path

import pytest

from chipbench.harness import common

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_its_files_by_name(cell):
    c = common.Cell(BENCH, cell)
    assert c.spec["name"] == c.config_name
    assert callable(c.config.program_config) and callable(c.ref.init)
    assert c.mix["kind"] == "federated"
    assert set(c.readers) == {m["name"] for m in c.per_layer}
    assert all(callable(r.read) for r in c.readers.values())
    assert c.limits and all(v > 0 for v in c.limits.values())
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer


def test_entries_keep_to_the_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert (REPO / p).is_dir()
    for entry in BENCH["configs"] + BENCH["workloads"] + \
            BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_qwen3_widths_are_the_registered_config_but_for_the_reduced_keys():
    from repro.configs.qwen3_0_6b import CONFIG
    c = common.Cell(BENCH, "qwen3-0.6b-cut4.fed-k4")
    cfg = c.config.program_config(c.spec)
    reduced = {"num_layers": "num_hidden_layers",
               "vocab_size": "vocab_size"}
    entry = {e["name"]: e for e in BENCH["configs"]}["qwen3-0.6b-cut4"]
    assert sorted(entry["reduced"]) == sorted(reduced.values())
    for field in ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
                  "qk_norm", "rope_theta", "tie_embeddings", "mlp_act",
                  "family"):
        assert getattr(cfg, field) == getattr(CONFIG, field), field
    for field, key in reduced.items():
        published, here = c.spec["reduced"][key]
        assert getattr(CONFIG, field) == published
        assert getattr(cfg, field) == here == c.spec[key]
    assert c.spec["vocab_size"] % 128 == 0
    assert c.spec["vocab_size"] >= CONFIG.vocab_size / 8


def test_vgg5_is_the_papers_table_iv_model():
    from repro.configs.vgg import VGG5
    c = common.Cell(BENCH, "vgg5.fedadapt-k64")
    cfg = c.config.program_config(c.spec)
    assert cfg.layers == VGG5.layers and cfg.ops == VGG5.ops
    assert (cfg.input_hw, cfg.input_ch, cfg.num_classes) == \
        (VGG5.input_hw, VGG5.input_ch, VGG5.num_classes)


def test_reference_weights_have_the_programs_structure():
    import jax
    from repro.models.split_program import get_split_program
    for cell in ("vgg5.fedadapt-k64", "qwen3-0.6b-cut4.fed-k4"):
        c = common.Cell(BENCH, cell)
        prog = get_split_program(c.config.program_config(c.spec))
        key = jax.random.PRNGKey(0)
        a = jax.eval_shape(prog.init, key)
        b = jax.eval_shape(lambda k: c.ref.init(c.spec, k), key)
        assert jax.tree_util.tree_structure(a) == \
            jax.tree_util.tree_structure(b)
        assert [x.shape for x in jax.tree_util.tree_leaves(a)] == \
            [x.shape for x in jax.tree_util.tree_leaves(b)]


def test_a_missing_file_is_an_error(tmp_path):
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"][0]["traffic"] = "no-such-mix"
    with pytest.raises(common.BenchError):
        common.Cell(bench, bench["workloads"][0]["name"])
