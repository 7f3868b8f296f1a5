"""The benchmark is driven by data: BENCHMARK.json names everything,
and a configuration, mix, metric, cell or architecture is added by new
files and new entries alone."""
import hashlib
import json
import re
import shutil

import numpy as np
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
        bench.architecture(conf)
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
        cfg = bench.architecture(conf).model_config(conf)
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


def test_llama_weights_are_bit_for_bit_those_of_before_the_move():
    """The Llama module's seeded weights at a tiny size, against the
    checksum that ``chipbench.model.init_weights`` gave for the same
    seed and size before the model builders moved into
    ``architectures/``."""
    import jax
    conf = dict(bench.config("yi-34b-l4"), name="tiny", hidden_size=64,
                intermediate_size=128, num_attention_heads=8,
                num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
                vocab_size=256)
    arch = bench.architecture(conf)
    params = arch.init_weights(arch.model_config(conf), 2**33 + 7)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == ("54035cc8d23cb8cf5314debe988d8b9e"
                             "f2b3d1616be207b119bf40edd441cad8")


#: an architecture the harness has no module for: GPT-style keys, equal
#: query and KV heads, a GELU MLP, served through the program's dense path
TOY_ARCH = """
import functools

from chipbench.model import prng_key


def model_config(conf):
    from repro.configs.base import ModelConfig
    d, h = conf["n_embd"], conf["n_head"]
    return ModelConfig(name=conf["name"], arch_type="dense",
                       source=conf["source"], num_layers=conf["n_layer"],
                       d_model=d, num_heads=h, num_kv_heads=h,
                       head_dim=d // h, d_ff=4 * d,
                       vocab_size=conf["vocab_size"], mlp_kind="gelu")


def weight_bytes(cfg):
    d, f, V, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.num_layers
    return 2 * (L * (4 * d * d + 2 * d * f + 2 * d) + 2 * V * d + d)


def _init(key, cfg):
    import jax
    import jax.numpy as jnp
    d, H, hd, f, V, L = (cfg.d_model, cfg.num_heads, cfg.head_dim,
                         cfg.d_ff, cfg.vocab_size, cfg.num_layers)
    ks = iter(jax.random.split(key, 12))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape) * std).astype(jnp.bfloat16)

    layer = {"ln1": normal((L, d), 0.1),
             "attn": {"wq": normal((L, d, H, hd), d ** -0.5),
                      "wk": normal((L, d, H, hd), d ** -0.5),
                      "wv": normal((L, d, H, hd), d ** -0.5),
                      "wo": normal((L, H, hd, d), d ** -0.5)},
             "ln2": normal((L, d), 0.1),
             "mlp": {"wi": normal((L, d, f), d ** -0.5),
                     "wo": normal((L, f, d), f ** -0.5)}}
    return {"embed": normal((V, d), 1.0), "final_norm": normal((d,), 0.1),
            "lm_head": normal((d, V), d ** -0.5), "prefix": (),
            "cycles": {"l0": layer}, "rest": ()}


def init_weights(cfg, seed):
    import jax
    return jax.jit(functools.partial(_init, cfg=cfg))(prng_key(seed))


def prefill_flops(cfg, n_pre, s):
    return 2 * s * cfg.num_layers * (4 * cfg.d_model ** 2
                                     + 2 * cfg.d_model * cfg.d_ff)


def decode_flops(cfg, context):
    return prefill_flops(cfg, context - 1, 1)
"""

#: its reference: a stand-in that finds every served token the best
TOY_REFERENCE = """
import numpy as np


def served_gaps(params, conf, served, pad_to, *, control=False):
    return [np.zeros(len(toks)) for _, _, toks in served]
"""


def toy_checkout(tmp_path, spec, arch="GeluToyForCausalLM"):
    """A copy of the benchmark under ``tmp_path`` with a cell of a toy
    architecture; returns the copy's ``chipbench`` directory and the
    bytes of every file that was there before."""
    here = tmp_path / "chipbench"
    shutil.copytree(bench.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(here): p.read_bytes()
              for p in here.rglob("*") if p.is_file()}
    conf = {"name": "toy-gpt", "source": "a test's own toy",
            "architectures": [arch], "reference": "toy", "n_embd": 32,
            "n_head": 4, "n_layer": 2, "vocab_size": 128, "reduced": {}}
    (here / "configs" / "toy-gpt.json").write_text(json.dumps(conf))
    (here / "architectures" / "GeluToyForCausalLM.py").write_text(TOY_ARCH)
    (here / "reference" / "toy.py").write_text(TOY_REFERENCE)
    mix = dict(bench.traffic("doc-qa"), documents=[16, 24],
               question_tokens=4, answer_tokens=3, clients=2)
    (here / "traffic" / "toy-mix.json").write_text(json.dumps(mix))
    (here / "limits" / "toy-cell.json").write_text(
        json.dumps({"widest_logit_gap": {"limit": 0.5}}))
    spec = json.loads(json.dumps(spec))
    spec["configs"].append({"name": "toy-gpt", "source": conf["source"],
                            "file": "chipbench/configs/toy-gpt.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "toy-cell", "config": "toy-gpt",
                              "traffic": "toy-mix", "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return here, before


def test_a_new_architecture_is_new_files(tmp_path, spec):
    """A toy architecture and its reference, added as files under a
    copy of the benchmark, are loaded and served through the harness
    with no existing file touched."""
    from repro.cluster.costmodel import CHIPS
    here, before = toy_checkout(tmp_path, spec)
    cell = run.load_cell("toy-cell", here)
    assert cell.here == here and cell.conf["n_embd"] == 32
    arch, cfg, params, tr, _, eng = run.build(cell, 2**33 + 3,
                                              CHIPS["tpu-v5e"])
    assert arch.__file__ == str(here / "architectures"
                                / "GeluToyForCausalLM.py")
    assert cfg.mlp_kind == "gelu" and cfg.num_kv_heads == cfg.num_heads
    assert params["cycles"]["l0"]["mlp"]["wi"].shape == (2, 32, 128)
    clients = run.Clients(eng, tr)
    eng.on_token = clients.on_token
    sent = clients.send(tr.asks[0][0])
    while not sent.done:
        assert clients.step() or sent.done
    assert len(sent.tokens) == 3 and sent.n_pre == 16
    assert run.check(cell, params, [sent], tr.longest_request) == 0.0
    after = {p.relative_to(here): p.read_bytes()
             for p in here.rglob("*") if p.is_file()
             and p.relative_to(here) in before}
    assert after == before


def test_an_architecture_with_no_module_stops_before_anything_runs(
        tmp_path, spec):
    here, _ = toy_checkout(tmp_path, spec, arch="NoSuchForCausalLM")
    want = "add chipbench/architectures/NoSuchForCausalLM.py giving " \
        "model_config, init_weights"
    with pytest.raises(FileNotFoundError, match=want):
        run.load_cell("toy-cell", here)
    with pytest.raises(FileNotFoundError, match=want):
        bench.architecture(bench.config("toy-gpt", here), here)
    # a module that lacks a function is refused as well
    (here / "architectures" / "NoSuchForCausalLM.py").write_text(
        "def model_config(conf):\n    return None\n")
    with pytest.raises(AttributeError, match="init_weights"):
        run.load_cell("toy-cell", here)
