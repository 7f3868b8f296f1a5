#!/usr/bin/env python
"""Chip smoke test: serve Yi-34B at its published widths on a TPU through
the live path of ``repro.launch.serve``, then check what came out.

    python chip_smoke.py             # one chip: the served path + checks
    python chip_smoke.py --chips 4   # four chips: KV-head-sharded engine
                                     # vs the same requests on one chip

One chip: Yi-34B cut to 4 of its 60 layers (every width as published,
seeded bf16 weights) serves a 2048-token donor prefix to three reuse
requests and one request without reuse (`serve_live`). The run fails
unless

  * both kernels on the path ran compiled for the TPU, not interpreted;
  * ``kv_restore`` and ``paged_attention`` match their ``ref.py``
    oracles on inputs from the served path;
  * every request's first-token logits match a full prefill of its
    prompt within the int8-KV tolerance (`repro.serving.verify`).

Four chips: only the engine whose paged KV is split by KV head over a
1x4 mesh (2 of Yi-34B's 8 KV heads per chip), compared on logits with
the same requests served on one chip in this process, within the
tolerance of the reductions the split reorders.

Every phase runs in this one process. With no TPU the script exits 1
before serving anything. The last line of standard output is the JSON
object ``{"ok": true, "device": {...}}``; it is printed only when every
check passed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent
ARCH, LAYERS = "yi-34b", 4


def log(msg: str) -> None:
    print(msg, flush=True)


def one_chip() -> None:
    from repro.configs import get_config
    from repro.launch.serve import cut_layers, serve_live
    from repro.serving import verify

    full = get_config(ARCH)
    cfg = cut_layers(full, LAYERS)
    log(f"cut: {ARCH} keeps {LAYERS} of {full.num_layers} layers")
    logits = verify.LogitsRecorder()
    run = serve_live(cfg, on_logits=logits)
    eng = run.engine
    for r in run.reuse + [run.plain]:
        if len(eng.outputs[r.rid]) != r.max_new_tokens:
            raise AssertionError(f"rid {r.rid}: {len(eng.outputs[r.rid])}"
                                 f" of {r.max_new_tokens} tokens")
    for r in run.reuse:
        if r.storage_hit != "full" or r.fetch_done is None:
            raise AssertionError(f"rid {r.rid}: prefix not fetched "
                                 f"(hit={r.storage_hit})")
    k = verify.check_kernels(run)
    if not (k["kv_restore_mosaic"] and k["paged_attention_mosaic"]):
        raise AssertionError(f"a kernel did not lower to Mosaic: {k}")
    log("kernels compiled (tpu_custom_call); vs ref.py: kv_restore max "
        f"|err| {k['kv_restore_max_abs_err']}, paged_attention max |err| "
        f"{k['paged_attention_max_abs_err']} (atol "
        f"{k['paged_attention_atol']})")
    tol = verify.kv_int8_logit_tolerance(cfg.num_layers,
                                         run.params["embed"].dtype)
    errs = verify.check_reuse_logits(run, logits)
    log(f"first-token logits vs full prefill, relative L2 (tolerance "
        f"{tol}): " + ", ".join(f"rid {r} {e}" for r, e in errs.items()))


def four_chips() -> None:
    import gc

    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.serve import cut_layers, serve_live
    from repro.serving import verify

    cfg = cut_layers(get_config(ARCH), LAYERS)
    log("== reference: the same requests on one chip ==")
    ref_logits = verify.LogitsRecorder()
    one = serve_live(cfg, on_logits=ref_logits)
    cluster = one.cluster
    ref = {r.rid: (ref_logits[r.rid], one.engine.outputs[r.rid])
           for r in one.reuse + [one.plain]}
    # free the one-chip weights and pages on device 0 before the mesh
    # replicates its own: the store still points at the old controller,
    # and the engine and its controller reference each other
    del one
    cluster.bind(None)
    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    log(f"device 0 after freeing the one-chip run: "
        f"{stats.get('bytes_in_use', 0) / 1e9:.2f} GB in use")
    mesh = make_debug_mesh(shape=(1, 4))
    log(f"== sharded: KV heads over mesh {dict(mesh.shape)} ==")
    logits = verify.LogitsRecorder()
    run = serve_live(cfg, mesh=mesh, cluster=cluster, on_logits=logits)
    pages = run.engine.cache.k_pages
    heads = {s.data.shape[3] for s in pages.addressable_shards}
    if heads != {cfg.num_kv_heads // 4}:
        raise AssertionError(f"KV heads per chip {heads}, want "
                             f"{cfg.num_kv_heads // 4}")
    log(f"page shards hold {heads.pop()} KV heads each on "
        f"{len(pages.addressable_shards)} devices")
    tol = verify.shard_logit_tolerance(cfg.num_layers,
                                       run.params["embed"].dtype)
    for r in run.reuse + [run.plain]:
        want_logits, want_tokens = ref[r.rid]
        err, steps = verify.check_streams(
            logits[r.rid], run.engine.outputs[r.rid],
            want_logits, want_tokens, tol, f"rid {r.rid} sharded vs one")
        log(f"rid {r.rid}: sharded vs one-chip logits, {steps} steps, max "
            f"relative L2 {err} (tolerance {tol})")


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the KV-head-sharded engine against "
                         "the same requests on one chip")
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    use_compile_cache()
    (four_chips if args.chips == 4 else one_chip)()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
