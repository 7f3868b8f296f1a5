"""One run of one benchmark cell on the chip.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

It builds the cell's model from its configuration file with seeded
bfloat16 weights (by the module of the configuration's architecture,
``architectures/<name>.py``), stores the traffic's documents from seeded
donor prefills where the mix reuses them, and serves through
`repro.serving.engine.LiveEngine` built as
`repro.launch.serve.serve_live` builds it (async fetch at 240p over a
modelled 16 Gbps link; the modelled link and the virtual clock only
order the fetch events, no virtual time enters a metric). It warms up
every shape the traffic uses, then drives closed-loop clients for
``--seconds``: each client sends its next request the moment its last
one finished. Every time is this process's host clock, read after the
device result is on the host. After the window it checks the served
tokens against the configuration's plain reference and prints one JSON
line. With ``--trace 1`` the window runs under the profiler and the
line carries the per-layer metrics instead of the end-to-end ones.

With no TPU, or fewer chips than the cell asks for, it exits non-zero
before anything is timed and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import pathlib
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import bench
from chipbench import traffic as traffic_mod

#: host clock at import, the fallback origin of ``setup_s``
T_IMPORT = time.perf_counter()
ROOT = bench.ROOT
CACHE_DIR = ROOT / ".jax_cache"
PAGE_SIZE = 16
LINK_GBPS = 16.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def clock() -> float:
    return time.perf_counter()


def process_age() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= age < 3600:
            return age
    except (OSError, ValueError, IndexError):
        pass
    return clock() - T_IMPORT


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def log_at(msg: str) -> None:
    """``log`` with the process's age, to place each step of set-up."""
    log(f"[{process_age():7.2f}s] {msg}")


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else the checkout's fixed ``.jax_cache/``. Every
    program is kept, however quick its compile: the served path
    dispatches op by op, and each op compiles in well under JAX's
    default one-second floor."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips_or_exit(n: int):
    """The first ``n`` TPU devices, or exit 2."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        log(f"no accelerator: {e}")
        raise SystemExit(2)
    if devs[0].platform != "tpu" or len(devs) < n:
        log(f"this cell needs {n} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s): no result")
        raise SystemExit(2)
    return devs[:n]


# -- what the window records ---------------------------------------------------

@dataclasses.dataclass
class Sent:
    """One request a client sent, and what came back, on the host clock."""
    rid: int
    t_send: float
    prompt: np.ndarray
    n_pre: int
    answer_tokens: int
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.answer_tokens


@dataclasses.dataclass
class Calls:
    """Kernel calls and model work seen while ``on`` (the traced
    window): the shapes the per-layer readers turn into bytes and
    operations. Arguments past the recorded ones go to the kernel
    untouched."""
    on: bool = False
    restore: List[tuple] = dataclasses.field(default_factory=list)
    attend: List[tuple] = dataclasses.field(default_factory=list)

    def wrap(self, cache) -> None:
        restore, attend = cache._restore, cache._attend

        def rec_restore(pages, q_tokens, scales, slots, *args, **kw):
            if self.on:
                self.restore.append((tuple(q_tokens.shape),
                                     np.dtype(pages.dtype).itemsize,
                                     np.dtype(q_tokens.dtype).itemsize,
                                     np.dtype(scales.dtype).itemsize))
            return restore(pages, q_tokens, scales, slots, *args, **kw)

        def rec_attend(q, k_pages, v_pages, block_tables, context_lens,
                       *args, **kw):
            if self.on:
                # context_lens stays on the device until the window ends
                self.attend.append((tuple(q.shape), tuple(k_pages.shape),
                                    np.dtype(k_pages.dtype).itemsize,
                                    context_lens))
            return attend(q, k_pages, v_pages, block_tables, context_lens,
                          *args, **kw)

        cache._restore, cache._attend = rec_restore, rec_attend


class Clients:
    """Closed-loop clients over one engine."""

    def __init__(self, eng, traffic: traffic_mod.Traffic):
        self.eng = eng
        self.traffic = traffic
        self.sent: Dict[int, Sent] = {}
        self.current: List[Optional[Sent]] = [None] * traffic.mix["clients"]
        self.next_ask = [0] * traffic.mix["clients"]
        self.decode_in_step = 0
        #: (host time, tokens this step decoded) per engine step
        self.steps: List[tuple] = []
        #: (host time, rid, index of the token in its answer)
        self.token_log: List[tuple] = []

    def on_token(self, req, token: int, t_virtual: float) -> None:
        now = clock()
        s = self.sent.get(req.rid)
        if s is None:
            return
        s.tokens.append(int(token))
        s.times.append(now)
        self.token_log.append((now, req.rid, len(s.tokens) - 1))
        if len(s.tokens) > 1:
            self.decode_in_step += 1

    def send(self, ask: traffic_mod.Ask) -> Sent:
        """Submit ``ask``, reusing its stored document where the mix
        reuses, else as a whole prompt to prefill."""
        prompt = self.traffic.prompt(ask)
        reuse = self.traffic.reuses
        n_pre = len(self.traffic.documents[ask.doc]) if reuse else 0
        t = clock()
        req = self.eng.submit(prompt,
                              reuse_prefix="by-tokens" if reuse else None,
                              reuse_tokens=n_pre,
                              max_new_tokens=ask.answer_tokens)
        s = Sent(req.rid, t, prompt, n_pre, ask.answer_tokens)
        self.sent[req.rid] = s
        return s

    def step(self) -> bool:
        import jax
        self.decode_in_step = 0
        with jax.profiler.TraceAnnotation("chipbench.step"):
            busy = self.eng.step()
        self.steps.append((clock(), self.decode_in_step))
        return busy

    def fill(self) -> None:
        """Every idle client sends its next request."""
        asks = self.traffic.asks
        for c in range(len(self.current)):
            cur = self.current[c]
            if cur is None or cur.done:
                if self.next_ask[c] >= len(asks[c]):
                    raise RuntimeError(
                        f"client {c} ran out of requests; raise "
                        "requests_per_client in the traffic mix")
                self.current[c] = self.send(asks[c][self.next_ask[c]])
                self.next_ask[c] += 1


def percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


# -- the run ---------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    conf: dict
    mix: dict
    limits: dict
    per_layer: List[str]
    #: the benchmark's directory, where its modules are found
    here: pathlib.Path = bench.HERE


def load_cell(workload_name: str, here: pathlib.Path = bench.HERE) -> Cell:
    """The cell's files, found by name under ``here``; a mix or an
    architecture the harness cannot take stops here, before anything
    is built or timed."""
    b = bench.benchmark(here.parent)
    wl = bench.workload(b, workload_name)
    conf = bench.config(wl["config"], here)
    mix = bench.traffic(wl["traffic"], here)
    traffic_mod.check_mix(mix)
    bench.architecture(conf, here)
    limits = bench.limits(workload_name, here)
    per_layer = [m["name"] for m in bench.metrics_for(b, workload_name, True)]
    return Cell(workload_name, conf, mix, limits, per_layer, here)


def build(cell: Cell, seed: int, chip=None):
    """The architecture module, weights, stored documents (none where
    the mix reuses nothing) and the engine; what set-up makes."""
    import jax
    from repro.cluster.costmodel import EngineCostModel, chip_for_device
    from repro.cluster.network import BandwidthTrace
    from repro.cluster.storage import StorageCluster, StorageNode
    from repro.serving import paged_model
    from repro.serving.engine import LiveEngine

    arch = bench.architecture(cell.conf, cell.here)
    cfg = arch.model_config(cell.conf)
    dev = jax.devices()[0]
    chip = chip if chip is not None else chip_for_device(dev.device_kind)
    t = clock()
    params = arch.init_weights(cfg, seed)
    jax.block_until_ready(params)
    log_at(f"weights: {cfg.name} {cfg.num_layers} layers, "
        f"{arch.weight_bytes(cfg) / 1e9:.3f} GB bfloat16 "
        f"({clock() - t:.2f}s)")
    tr = traffic_mod.generate(cell.mix, seed, cfg.vocab_size)
    t = clock()
    cluster = StorageCluster([StorageNode("n0")])
    if tr.reuses:
        enc = 0
        for doc in tr.documents:
            kv_k, kv_v = paged_model.donor_prefix_kv(params, cfg, doc)
            entry = cluster.register_prefix(doc, kv_k, kv_v,
                                            resolutions=("240p",))
            enc += entry.manifest.total_bytes("240p")
        log_at(f"documents: {[len(d) for d in tr.documents]} tokens stored, "
            f"{enc / 1e6:.2f} MB at 240p ({clock() - t:.2f}s)")
    else:
        log(f"contexts: {[len(d) for d in tr.documents]} tokens, nothing "
            "stored: every request is prefilled whole")
    pages_per_req = -(-tr.longest_request // PAGE_SIZE)
    eng = LiveEngine(params, cfg, cluster,
                     n_pages=cell.mix["clients"] * pages_per_req + 1,
                     page_size=PAGE_SIZE, policy="kvfetcher",
                     max_running=cell.mix["clients"], fetch_mode="async",
                     bandwidth=BandwidthTrace.constant(LINK_GBPS),
                     adaptive=False, resolution="240p",
                     resolutions=("240p",),
                     cost=EngineCostModel(cfg, chip, 1))
    log_at(f"memory held: weights {arch.weight_bytes(cfg)} B, page pool "
        f"{eng.cache.gpu_bytes()} B ({eng.cache.n_pages} pages); the peak "
        "adds the transient copies of the eager path")
    return arch, cfg, params, tr, cluster, eng


def warm_up(clients: Clients, seed: int) -> None:
    """Touch every program the window runs. The engine serves
    `traffic.warmup_asks` (fetch, host decode, restore, suffix prefill
    and first token of each document, or the whole prefill of each
    prompt where nothing is reused); then `warm_decode` runs one
    decode step of every shape the window's batches take."""
    import jax
    with jax.profiler.TraceAnnotation("chipbench.warmup"):
        batch = [clients.send(a)
                 for a in traffic_mod.warmup_asks(clients.traffic, seed)]
        while not all(s.done for s in batch):
            if not clients.step():
                break
        if not all(s.done for s in batch):
            raise RuntimeError("a warm-up request was never served")
        warm_decode(clients.eng, clients.traffic)
    clients.sent.clear()
    clients.steps.clear()
    clients.token_log.clear()


#: sequence ids of `warm_decode`, above any the engine hands out
WARM_SEQ = 1 << 40


def warm_decode(eng, tr: traffic_mod.Traffic) -> None:
    """One decode step, as `LiveEngine.step` makes it, at every batch
    size from 1 to the clients and every block-table width a request of
    the window has. A decode batch's programs are shaped by its size and
    by the pages of its widest sequence, which a request holds for its
    prompt and whole answer; the sequences here hold pages and are
    released, so the engine's own state is untouched."""
    import jax.numpy as jnp
    from repro.serving import paged_model
    cache = eng.cache
    ps = cache.page_size
    for pages in sorted({-(-n // ps) for n in tr.request_lengths}):
        for b in range(1, tr.mix["clients"] + 1):
            ids = [WARM_SEQ + i for i in range(b)]
            for sid in ids:
                cache.add_seq(sid, pages * ps)
            toks = jnp.asarray([0] * b, jnp.int32)
            pos = jnp.asarray([pages * ps - 1] * b, jnp.int32)
            try:
                logits = paged_model.decode_paged(eng.params, eng.cfg, toks,
                                                  pos, cache, ids)
                np.asarray(jnp.argmax(logits, axis=-1))
            finally:
                for sid in ids:
                    cache.free_seq(sid)


def drive(clients: Clients, seconds: float, grace: float, start, stop):
    """The window: closed-loop clients for ``seconds``, then serve what
    was sent until done or ``grace`` seconds past the close. ``start``
    and ``stop`` are called as the window opens and closes. Returns
    (t0, t_end, t_stop): window start, close, and when it was closed."""
    import jax
    start()
    ann = jax.profiler.TraceAnnotation("chipbench.window")
    ann.__enter__()
    t0 = clock()
    t_end = t0 + seconds
    t_stop = None
    while True:
        now = clock()
        if now < t_end:
            clients.fill()
        else:
            if t_stop is None:
                t_stop = now
                ann.__exit__(None, None, None)
                stop()
            if all(s.done for s in clients.sent.values()) \
                    or now > t_end + grace:
                break
        clients.step()
    return t0, t_end, t_stop


def end_to_end(clients: Clients, t0: float, t_end: float) -> dict:
    sent = [s for s in clients.sent.values() if t0 <= s.t_send < t_end]
    ttft = [s.times[0] - s.t_send for s in sent if s.times]
    gaps = [b - a for s in sent for a, b in zip(s.times, s.times[1:])]
    n_tok = sum(1 for s in clients.sent.values() for t in s.times
                if t0 <= t <= t_end)
    out = {}
    if ttft:
        out["ttft_p50_s"] = percentile(ttft, 50)
        out["ttft_p95_s"] = percentile(ttft, 95)
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    out["output_tok_s"] = n_tok / (t_end - t0)
    return out


def describe_window(clients: Clients, t0: float, t_end: float) -> None:
    """Log what the end-to-end metrics are taken from: every TTFT and
    the inter-token gaps' quantiles."""
    sent = [s for s in clients.sent.values() if t0 <= s.t_send < t_end]
    ttft = sorted(s.times[0] - s.t_send for s in sent if s.times)
    gaps = [b - a for s in sent for a, b in zip(s.times, s.times[1:])]
    log(f"window: ttft_s {[round(t, 3) for t in ttft]}")
    if gaps:
        q = np.percentile(np.asarray(gaps) * 1e3, [10, 50, 90, 95, 99, 100])
        log(f"window: {len(gaps)} gaps, ms at p10/p50/p90/p95/p99/max "
            f"{[round(float(x), 1) for x in q]}")


def sample_for_check(clients: Clients, t0: float, t_end: float, n: int,
                     seed: int) -> List[Sent]:
    """``n`` finished requests of the window drawn from the seed, with a
    request of the longest prompt among them."""
    done = sorted((s for s in clients.sent.values()
                   if t0 <= s.t_send < t_end and s.done),
                  key=lambda s: s.rid)
    if not done:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xc4ec]))
    pick = [done[i] for i in sorted(rng.choice(len(done), min(n, len(done)),
                                               replace=False))]
    longest = max(len(s.prompt) for s in done)
    if all(len(s.prompt) != longest for s in pick):
        cands = [s for s in done if len(s.prompt) == longest]
        pick[0] = cands[int(rng.integers(len(cands)))]
    return pick


def check(cell: Cell, params, sample: List[Sent], pad_to: int,
          control: bool = False) -> Optional[float]:
    """Widest gap between the reference's best logit and its logit of a
    served token, over the sample (None if the sample is empty)."""
    ref = bench.reference(cell.conf["reference"], cell.here)
    gaps = ref.served_gaps(params, cell.conf,
                           [(s.prompt, s.n_pre, s.tokens) for s in sample],
                           pad_to, control=control)
    return max((float(g.max()) for g in gaps), default=None)


@dataclasses.dataclass
class Served:
    """What one served window leaves for the report and the check."""
    cell: Cell
    arch: object  # the configuration's architecture module
    cfg: object
    params: dict
    traffic: traffic_mod.Traffic
    clients: Clients
    calls: Calls
    t0: float
    t_end: float
    t_stop: float
    setup_s: float
    compiles: int
    log_dir: Optional[str]

    @property
    def sent(self) -> List[Sent]:
        return [s for s in self.clients.sent.values()
                if self.t0 <= s.t_send < self.t_end]

    @property
    def failed(self) -> int:
        return sum(1 for s in self.sent if not s.done)

    def release(self) -> None:
        """Free the program's state (engine, pages, stored documents)."""
        self.clients.eng = None
        gc.collect()


def serve(cell: Cell, seed: int, seconds: float, trace: bool, *,
          chip=None) -> Served:
    """Set-up, warm-up and the window; with ``trace`` the window runs
    under the profiler and the kernel calls in it are recorded."""
    import jax

    compiles: List[float] = []
    window_open = [False]

    def on_event(name, secs, **kw):
        if name == COMPILE_EVENT and window_open[0]:
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)

    arch, cfg, params, tr, cluster, eng = build(cell, seed, chip)
    clients = Clients(eng, tr)
    eng.on_token = clients.on_token
    del eng, cluster  # the clients hold the engine, the engine the store
    calls = Calls()
    if trace:
        calls.wrap(clients.eng.cache)
    t = clock()
    warm_up(clients, seed)
    log_at(f"warm-up: {clock() - t:.2f}s")

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None

    def start():
        if trace:
            # host spans (TraceAnnotation) only: the Python tracer would
            # slow every Python call of the host codec it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        calls.on = True
        window_open[0] = True

    def stop():
        calls.on = False
        window_open[0] = False
        if trace:
            jax.profiler.stop_trace()

    setup_s = process_age()
    t0, t_end, t_stop = drive(clients, seconds,
                              float(cell.mix["grace_seconds"]), start, stop)
    served = Served(cell, arch, cfg, params, tr, clients, calls, t0,
                    t_end, t_stop, setup_s, len(compiles), log_dir)
    log(f"requests: {len(served.sent)} attempted, "
        f"{len(served.sent) - served.failed} finished, {served.failed} "
        f"failed; {served.compiles} compiles in the window")
    return served


def per_layer(served: Served, device_kind: str, peaks=None):
    """The traced window's per-layer metrics, device busy time and
    breakdown; the trace is deleted once read."""
    from chipbench import trace as trace_mod
    from chipbench.metrics_context import Context
    try:
        ptrace = trace_mod.load(trace_mod.find_xplane(served.log_dir))
    finally:
        _rmtree(served.log_dir)
    win = [e for e in ptrace.host if e.name == "chipbench.window"]
    window_ns = (win[0].start_ns, win[0].end_ns) if win else None
    ctx = Context(trace=ptrace, window_ns=window_ns,
                  window_s=served.t_stop - served.t0, cfg=served.cfg,
                  arch=served.arch,
                  peaks=peaks or bench.peaks(device_kind),
                  restore_calls=served.calls.restore,
                  attend_calls=[c[:3] + (np.asarray(c[3]),)
                                for c in served.calls.attend],
                  clients=served.clients, t0=served.t0,
                  t_stop=served.t_stop, compiles=served.compiles)
    values = {}
    for name in served.cell.per_layer:
        v = bench.metric_reader(name).read(ctx)
        if v is not None:
            values[name] = float(v)
    busy = {"busy_s": trace_mod.busy_seconds(ptrace),
            "window_s": ((window_ns[1] - window_ns[0]) / 1e9 if window_ns
                         else served.t_stop - served.t0)}
    breakdown = {"device_ops": trace_mod.top_ops(ptrace),
                 "idle_gaps": (trace_mod.idle_gaps(ptrace, window_ns)
                               if window_ns else [])}
    return values, busy, breakdown


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             devices, chip=None, peaks=None) -> dict:
    """Everything after the device check; returns the result line's
    object."""
    served = serve(cell, seed, seconds, trace, chip=chip)
    dev = devices[0]
    peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices))
    log(f"memory: peak_bytes_in_use {peak} on the fullest chip")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    spec = bench.benchmark(cell.here.parent)
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    breakdown = None
    if trace:
        values, busy, breakdown = per_layer(served, dev.device_kind, peaks)
        device.update(busy)
    else:
        values = end_to_end(served.clients, served.t0, served.t_end)
        describe_window(served.clients, served.t0, served.t_end)
        values["setup_s"] = served.setup_s
    metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()}

    sample = sample_for_check(served.clients, served.t0, served.t_end,
                              int(cell.mix["check_requests"]), seed)
    served.release()
    t = clock()
    gap = check(cell, served.params, sample, served.traffic.longest_request)
    log(f"reference over {len(sample)} requests, "
        f"{sum(len(s.tokens) for s in sample)} tokens: {clock() - t:.2f}s")
    limit = float(cell.limits["widest_logit_gap"]["limit"])
    checks = {
        "widest_logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": served.failed, "limit": 0},
        "checked_requests": {"value": len(sample), "limit": 1},
    }
    correct = (gap is not None and gap <= limit and served.failed == 0
               and len(sample) >= 1)
    out = {"correct": bool(correct), "attempted": len(served.sent),
           "failed": served.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = checks
    return out


def _rmtree(path: str) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)


def print_result(out: dict) -> None:
    for k, c in out["check"].items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    log_at("started")
    cell = load_cell(args.workload)
    wl = bench.workload(bench.benchmark(), args.workload)
    use_compile_cache()
    log_at("jax imported")
    devices = chips_or_exit(int(wl["chips"]))
    dev = devices[0]
    log_at(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    log(f"config: {cell.conf['name']} ({cell.conf['source']}), reduced "
        f"{sorted(cell.conf['reduced'])}")
    import jax
    with jax.default_device(dev):
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       devices=devices)
    print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
