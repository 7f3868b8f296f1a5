"""Serving launcher: the live engine at a model's published widths, or the
cluster simulation.

    PYTHONPATH=src python -m repro.launch.serve --live --arch yi-34b --layers 4
    PYTHONPATH=src python -m repro.launch.serve --arch yi-34b \
        --simulate --gbps 16 --context 100000 --method kvfetcher

``--live`` serves seeded random weights of ``--arch`` with every width as
published and only the depth cut to ``--layers``. One donor prefill
registers a prefix in a `StorageCluster`; reuse requests then fetch it
over a modelled WAN link (async fetch on the virtual clock), restore it
into paged memory with the ``kv_restore`` kernel, prefill their
suffixes and decode with the ``paged_attention`` kernel, next to one
request that reuses nothing. `serve_live` is the same path as a
function; ``chip_smoke.py`` calls it and checks what it returns.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Dict, Optional

import numpy as np

from repro.configs.base import ModelConfig

SEED = 0  # weights, prompts and suffixes all come from it
N_REUSE = 3  # requests that reuse the donor prefix


@dataclasses.dataclass
class LiveRun:
    """What `serve_live` served."""
    engine: object  # LiveEngine: outputs, clocks, stats
    params: dict
    cfg: ModelConfig
    cluster: object  # StorageCluster holding the registered prefix
    prefix: np.ndarray
    reuse: list  # Requests that fetched the prefix
    plain: object  # the Request that reused nothing
    #: host clock per phase; every phase ends on a device-to-host copy
    phase_seconds: Dict[str, float]


def cut_layers(cfg: ModelConfig, layers: Optional[int]) -> ModelConfig:
    """``cfg`` with only its depth cut to ``layers``; every width stays
    as published."""
    if layers is None:
        return cfg
    if not 1 <= layers <= cfg.num_layers:
        raise ValueError(f"--layers {layers}: {cfg.name} has "
                         f"{cfg.num_layers} layers")
    return dataclasses.replace(cfg, num_layers=layers)


def init_weights(cfg: ModelConfig, sharding=None):
    """Seeded random bf16 weights made on the device in one program;
    ``sharding`` places them (e.g. replicated over a mesh)."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tf
    init = jax.jit(functools.partial(tf.init_params, cfg,
                                     dtype=jnp.bfloat16),
                   out_shardings=sharding)
    return init(jax.random.PRNGKey(SEED))


def serve_live(cfg: ModelConfig, *, prefix_len: int = 2048,
               suffix_len: int = 64, new_tokens: int = 16,
               plain_len: int = 256, gbps: float = 16.0, chip=None,
               mesh=None, cluster=None, on_logits=None) -> LiveRun:
    """Serve ``N_REUSE`` requests that reuse one ``prefix_len``-token
    prefix (each with its own ``suffix_len``-token suffix) and one
    ``plain_len``-token request that reuses nothing, ``new_tokens``
    tokens each, through `LiveEngine` in async fetch mode.

    ``chip`` is the `ChipSpec` of the virtual clock's compute model; by
    default it is looked up from the device JAX runs on, and an
    unknown device is an error. ``mesh`` splits the paged KV over its
    "model" axis by KV head and replicates the weights. ``cluster``
    reuses a store that already holds the prefix (skipping the donor).
    ``on_logits`` is passed to `LiveEngine` (e.g. a
    `repro.serving.verify.LogitsRecorder`).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.cluster.costmodel import EngineCostModel, chip_for_device
    from repro.cluster.network import BandwidthTrace
    from repro.cluster.storage import StorageCluster, StorageNode
    from repro.serving import paged_model
    from repro.serving.engine import LiveEngine

    dev = jax.devices()[0]
    chip = chip if chip is not None else chip_for_device(dev.device_kind)
    n_dev = 1 if mesh is None else mesh.size
    phase: Dict[str, float] = {}

    def log(msg: str) -> None:
        print(msg, flush=True)

    def clock() -> float:
        # host-clock phase times, reported apart from the virtual clock;
        # no replayed event log reads them
        return time.perf_counter()  # repro-lint: allow(no-wall-clock)

    t0 = clock()
    sharding = None if mesh is None else NamedSharding(mesh,
                                                       PartitionSpec())
    params = init_weights(cfg, sharding)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    jax.block_until_ready(params)
    phase["init_weights"] = clock() - t0
    log(f"device: {dev.platform} {dev.device_kind} x{n_dev}; "
        f"virtual clock models {chip.name} compute")
    log(f"model: {cfg.name} {cfg.num_layers} layers "
        f"(d_model {cfg.d_model}, heads {cfg.num_heads}/"
        f"{cfg.num_kv_heads}, head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}); bfloat16 weights "
        f"{nbytes / 1e9:.2f} GB")

    rng = np.random.default_rng(SEED)
    prefix = rng.integers(0, cfg.vocab_size, prefix_len)
    if cluster is None:
        t0 = clock()
        kv_k, kv_v = paged_model.donor_prefix_kv(params, cfg, prefix)
        phase["donor_prefill"] = clock() - t0
        t0 = clock()
        cluster = StorageCluster([StorageNode("n0")])
        entry = cluster.register_prefix(prefix, kv_k, kv_v,
                                        resolutions=("240p",))
        phase["encode_register"] = clock() - t0
        enc = entry.manifest.total_bytes("240p")
        log(f"donor: {prefix_len}-token prefix -> "
            f"{len(entry.manifest.refs)} chunks, {enc / 1e6:.2f} MB at "
            f"240p ({(kv_k.nbytes + kv_v.nbytes) / enc:.1f}x vs "
            f"{kv_k.dtype} KV)")

    prompts = [np.concatenate([prefix,
                               rng.integers(0, cfg.vocab_size, suffix_len)])
               for _ in range(N_REUSE)]
    plain_prompt = rng.integers(0, cfg.vocab_size, plain_len)
    ps = 16  # tokens per page
    pages = sum(-(-(len(p) + new_tokens) // ps)
                for p in prompts + [plain_prompt])
    eng = LiveEngine(params, cfg, cluster, n_pages=pages + 1,
                     page_size=ps, policy="kvfetcher",
                     max_running=N_REUSE + 1, fetch_mode="async",
                     bandwidth=BandwidthTrace.constant(gbps),
                     adaptive=False, resolution="240p",
                     resolutions=("240p",),
                     # one chip's compute even on a mesh: the weights
                     # are replicated and every chip runs every layer
                     cost=EngineCostModel(cfg, chip, 1), mesh=mesh,
                     on_logits=on_logits)
    reuse = [eng.submit(p, reuse_prefix="by-tokens",
                        reuse_tokens=prefix_len, max_new_tokens=new_tokens)
             for p in prompts]
    plain = eng.submit(plain_prompt, max_new_tokens=new_tokens)
    t0 = clock()
    eng.run()
    phase["serve"] = clock() - t0
    for r in reuse + [plain]:
        log(f"  rid={r.rid} prompt={r.prompt_len} reuse={r.reuse_tokens} "
            f"hit={r.storage_hit} tokens={len(eng.outputs[r.rid])} "
            f"virtual_ttft={r.ttft:.4f}s")
    log("host clock: " + ", ".join(f"{k} {v:.2f}s"
                                   for k, v in phase.items()))
    return LiveRun(eng, params, cfg, cluster, prefix, reuse, plain, phase)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lwm-7b")
    ap.add_argument("--live", action="store_true")
    ap.add_argument("--simulate", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="live: keep this many layers (widths stay as "
                         "published)")
    ap.add_argument("--method", default="kvfetcher",
                    choices=["kvfetcher", "cachegen", "llm265", "raw",
                             "lmcache_raw", "full_prefill"])
    ap.add_argument("--gbps", type=float, default=16.0)
    ap.add_argument("--context", type=int, default=100_000)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--chip", default=None,
                    choices=["h20", "a100", "l20", "tpu-v5e"],
                    help="simulate: compute model of the clock (default "
                         "h20); --live models the device JAX runs on")
    args = ap.parse_args()

    from repro.configs import get_config

    if args.live or not args.simulate:
        if args.chip is not None:
            ap.error("--chip applies to --simulate only; --live takes "
                     "its cost model from the device JAX runs on")
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        cfg = get_config(args.arch)
        run = serve_live(cut_layers(cfg, args.layers), gbps=args.gbps)
        print(f"{cfg.name}: kept {run.cfg.num_layers} of "
              f"{cfg.num_layers} layers; served "
              f"{len(run.engine.finished)} requests")
        return

    if args.chip == "tpu-v5e":
        raise SystemExit(
            "--simulate --chip tpu-v5e: no TPU v5e decode table is "
            "measured (the tables are the paper's GPU NVDEC "
            "measurements); simulate h20, a100 or l20")

    from repro.core.adaptive import TABLES
    from repro.cluster.network import BandwidthTrace
    from repro.cluster import simulator as sim
    from repro.data.workload import fixed_context_trace
    from repro.serving.metrics import summarize

    chip = args.chip or "h20"
    spec = {
        "kvfetcher": sim.kvfetcher_spec(
            {"240p": 9.0, "480p": 8.5, "640p": 8.0, "1080p": 7.0}),
        "cachegen": sim.cachegen_spec(3.5),
        "llm265": sim.llm265_spec(5.0),
        "raw": sim.raw_spec(),
        "lmcache_raw": sim.lmcache_raw_spec(),
        "full_prefill": sim.full_prefill_spec(),
    }[args.method]
    s = sim.ServingSimulator(
        get_config(args.arch), spec, chip=chip, n_chips=2,
        bandwidth=BandwidthTrace.constant(args.gbps), table=TABLES[chip])
    res = s.run(fixed_context_trace(args.context,
                                    n_requests=args.requests, gap=60.0),
                max_new_tokens=16)
    reqs = res.fetching() or res.requests
    print(f"method={args.method} ctx={args.context} bw={args.gbps}Gbps")
    for k, v in summarize(reqs).items():
        print(f"  {k}: {v:.3f}")


if __name__ == "__main__":
    main()
