"""BENCHMARK.json and the files it names keep to the benchmark's rules."""
import json
import re
from pathlib import Path

import pytest

from chipbench import counts
from chipbench.references import dense_gqa

REPO = Path(__file__).resolve().parents[2]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert (REPO / p).is_dir() and not p.startswith("/") and ".." not in p
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        assert set(e) - {"workloads"} == KEYS[section], e
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")


def test_metrics_and_cells_agree():
    cells = {w["name"]: w for w in SPEC["workloads"]}
    configs = {c["name"] for c in SPEC["configs"]}
    assert {w["config"] for w in cells.values()} == configs
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
        assert (REPO / "chipbench" / "metrics" / f"{m['name']}.py").exists()
    for w in cells:
        reported = [m for m in SPEC["end_to_end"]
                    if w in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(w in m.get("workloads", cells) for m in SPEC["per_layer"])
    four = sum(w["chips"] == 4 for w in cells.values())
    assert four <= max(1, len(cells) // 2)


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(conf):
    """The file holds the published keys as run; what differs from the
    source is listed in ``reduced``, and the ``as_run`` block the
    reference and the counts read says the same."""
    c = json.loads((REPO / conf["file"]).read_text())
    assert c["source"] == conf["source"]
    assert set(c["published"]) == set(conf["reduced"])
    for k, v in c["published"].items():
        assert c[k] != v
    run = c["as_run"]
    alias = {"d_model": ("hidden_size",),
             "n_heads": ("num_attention_heads",),
             "n_kv_heads": ("num_key_value_heads", "multi_query_group_num"),
             "head_dim": ("head_dim", "kv_channels"),
             "d_ff": ("intermediate_size", "ffn_hidden_size"),
             "vocab": ("vocab_size", "padded_vocab_size"),
             "n_layers": ("num_hidden_layers", "num_layers")}
    for key, names in alias.items():
        got = [c[n] for n in names if n in c]
        assert got == [run[key]], (key, got)
    dense_gqa.Arch.of(run)
    counts.Dims.of(run)
    assert not run["qkv_bias"]
    assert c.get("add_qkv_bias", c.get("attention_bias")) is False
