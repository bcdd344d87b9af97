"""``BENCHMARK.json``: every cell resolves its files by name, names and
units keep to the allowed characters, and each configuration file states
its cut."""
import json
import re

import pytest

from bench_paths import BENCH_DIR

from chipbench import spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
# keys that are widths may never be cut
WIDTHS = {"d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
          "vocab_size"}


def config(name):
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


def test_keys_exactly_as_the_contract_has_them():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_every_cell_resolves_its_files():
    assert spec.problems(BENCH) == []
    for cell in CELLS:
        c = spec.resolve(BENCH, cell)
        assert c.per_layer and any(m["name"] == "setup_s"
                                   for m in c.end_to_end)
        assert len(c.end_to_end) >= 2
        ref, cost = spec.family_modules(c.config)
        assert hasattr(ref, "logits_at") and hasattr(cost, "decode_flops")


def test_names_units_and_text_fields():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    every = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
             + BENCH["per_layer"])
    names = [x["name"] for x in every]
    assert all(name.match(n) for n in names)
    for kind in ("configs", "workloads"):
        ns = [x["name"] for x in BENCH[kind]]
        assert len(ns) == len(set(ns))
    metric_names = [m["name"] for m in BENCH["end_to_end"]
                    + BENCH["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in {"lower", "higher"}
    for x in every:
        for key in ("why", "layer", "source"):
            if key in x:
                assert 1 <= len(x[key]) <= 200 and "\n" not in x[key] \
                    and "\t" not in x[key]
    for w in BENCH["workloads"]:
        assert name.match(w["config"]) and name.match(w["traffic"])
        assert w["chips"] in (1, 4)
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_per_layer_metrics_move_a_metric_their_cells_report():
    for m in BENCH["per_layer"]:
        for cell in m.get("workloads", CELLS):
            c = spec.resolve(BENCH, cell)
            assert m["moves"] in {e["name"] for e in c.end_to_end}
            assert m["name"] in {p["name"] for p in c.per_layer}
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(len(x.split()) <= 6 for x in layers)


def test_command_and_paths():
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    for p in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) and ".." not in p
        assert (spec.CHECKOUT / p).is_dir()
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])


def test_run_seconds_fit_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("name", CONFIGS)
def test_configuration_states_its_cut(name):
    entry = spec.config_entry(BENCH, name)
    c = config(name)
    assert entry["file"] == f"benchmarks/chip/configs/{name}.json"
    assert c["name"] == name
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) \
        == sorted(c["published"])
    assert not WIDTHS & set(entry["reduced"])
    for key, published in c["published"].items():
        assert c["model"][key] != published
    assert c["deployment"]["chips"] == 1
    from repro.configs.base import ModelConfig
    ModelConfig(**c["model"])           # the program takes it as it is


@pytest.mark.parametrize("cell", CELLS)
def test_every_request_fits_the_cache(cell):
    c = spec.resolve(BENCH, cell)
    dep, mix = c.config["deployment"], c.traffic
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest < dep["max_seq"]
    assert mix["clients"] == dep["slots"]       # no request waits for a slot
