"""The benchmark is driven by data: BENCHMARK.json names everything,
and a configuration, mix, metric or cell is added by new files and new
entries alone."""
import json
import re
import shutil

import pytest

from chipbench import bench, model, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    return bench.benchmark()


def test_benchmark_names_only_files_that_exist(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["chipbench", "tests/chipbench"]
    for c in spec["configs"]:
        conf = bench.config(c["name"])
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert sorted(c["reduced"]) == sorted(conf["reduced"])
        assert conf["source"] == c["source"]
        bench.reference(conf["reference"])
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        bench.config(w["config"]), bench.traffic(w["traffic"])
        assert bench.limits(w["name"])["widest_logit_gap"]["limit"] > 0
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    for m in spec["per_layer"]:
        assert callable(bench.metric_reader(m["name"]).read)
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
    assert {m["name"] for m in spec["end_to_end"]} == {
        "ttft_p50_s", "ttft_p95_s", "itl_p95_ms", "output_tok_s", "setup_s"}


def test_configs_keep_every_published_width(spec):
    for c in spec["configs"]:
        conf = bench.config(c["name"])
        assert list(conf["reduced"]) == ["num_hidden_layers"]
        cfg = model.model_config(conf)
        assert cfg.d_model == conf["hidden_size"]
        assert cfg.num_heads * cfg.head_dim == conf["hidden_size"]
        kv = 2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim * 2
        assert kv == conf["kv_bytes_per_token"]


def test_new_files_are_found_by_name_with_no_edit(tmp_path, spec):
    here = tmp_path / "chipbench"
    shutil.copytree(bench.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(here): p.read_bytes()
              for p in here.rglob("*") if p.is_file()}
    conf = dict(bench.config("yi-9b-l8"), name="extra-model")
    (here / "configs" / "extra-model.json").write_text(json.dumps(conf))
    mix = dict(bench.traffic("doc-qa"), answer_tokens=7)
    (here / "traffic" / "extra-mix.json").write_text(json.dumps(mix))
    (here / "metrics" / "extra.metric.py").write_text(
        "def read(ctx):\n    return 41.5\n")
    (here / "limits" / "extra-cell.json").write_text(
        json.dumps({"widest_logit_gap": {"limit": 0.5}}))
    assert bench.config("extra-model", here)["name"] == "extra-model"
    assert bench.traffic("extra-mix", here)["answer_tokens"] == 7
    assert bench.metric_reader("extra.metric", here).read(None) == 41.5
    assert bench.limits("extra-cell", here)["widest_logit_gap"]["limit"] \
        == 0.5
    # a new cell and metric are entries of BENCHMARK.json
    spec2 = json.loads(json.dumps(spec))
    spec2["workloads"].append({"name": "extra-cell", "config": "extra-model",
                               "traffic": "extra-mix", "chips": 1,
                               "why": "x"})
    spec2["per_layer"].append({"name": "extra.metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "ttft_p50_s",
                               "workloads": ["extra-cell"]})
    names = [m["name"] for m in bench.metrics_for(spec2, "extra-cell", True)]
    assert "extra.metric" in names and "kv_restore_roofline" not in names
    assert "extra.metric" not in [m["name"] for m in bench.metrics_for(
        spec2, "yi34b-doc-qa", True)]
    assert bench.workload(spec2, "extra-cell")["config"] == "extra-model"
    after = {p.relative_to(here): p.read_bytes()
             for p in here.rglob("*") if p.is_file()
             and p.relative_to(here) in before}
    assert after == before  # nothing that was there changed


def test_unknown_names_and_device_kinds_are_errors(spec):
    with pytest.raises(KeyError, match="no workload"):
        bench.workload(spec, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        bench.config("no-such-config")
    with pytest.raises(KeyError, match="no peaks for device kind"):
        bench.peaks("TPU v99")
    with pytest.raises(KeyError):
        bench.peaks("source")
    p = bench.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    cell = run.load_cell("yi34b-doc-qa")
    assert cell.conf["name"] == "yi-34b-l4" and cell.mix["answer_tokens"] == 32


def test_seed_keeps_all_its_bits():
    import jax
    a = jax.random.key_data(model.prng_key(2**33 + 5))
    b = jax.random.key_data(model.prng_key(5))
    assert a.tolist() != b.tolist()
    assert a.tolist() == jax.random.key_data(model.prng_key(2**33 + 5)).tolist()
    with pytest.raises(ValueError):
        model.seed_words(-1)
