"""Live serving engine: real compute, real codec, real paged memory.

This is the integration proof of the full KVFetcher path on actual small
models: fetching-aware scheduling, background fetch with frame-wise
restoration into paged memory via the Pallas kernel, suffix prefill over
restored prefix KV, and continuously-batched paged decode.

Fetching runs through the event-driven `repro.core.fetch_controller` —
the same transmit -> decode -> restore pipeline state machine the
cluster simulator uses.  Two operating modes:

  * wall clock (default, ``bandwidth=None``): fetches complete
    synchronously at dispatch, timestamps are ``time.monotonic()`` — the
    original engine behaviour, kept for integration tests.
  * virtual clock (``bandwidth=`` a BandwidthTrace): network transmit
    and decode latencies are modeled on a virtual clock while the codec
    and paged-memory mechanics stay real.  ``fetch_mode="async"`` pumps
    the controller from ``step()`` so restoration overlaps compute and a
    request can start suffix prefill while later layer groups are still
    in flight (Appx A.3 early admission); ``fetch_mode="sync"`` drains
    the pipeline serially at dispatch — the pre-pipelining baseline.

In virtual-clock mode the network is a WAN-grade model: concurrent
fetches split the trace via `repro.cluster.network.SharedLink` (weighted
``fair`` fluid sharing or ``drr`` chunk round-robin, ``link_policy=``;
``link_ramp="slowstart"`` shapes joins like a congestion window) and a
seeded ``loss=`` `LossModel` (including cross-flow correlated bursts)
drops chunk attempts which the controller retransmits under a per-flow
Jacobson/Karels adaptive timeout (``rto_mode=``) — restoration stays
bit-exact, only timing moves.

The ``store`` may be a flat `KVStore` or a multi-node `StorageCluster`
(docs/storage_tier.md): with a cluster, every fetch resolves through a
longest-prefix-match over the prompt tokens — full hit, partial
(ancestor) hit with tail recompute, or miss with full-prefill fallback —
and transmits over the serving node's own link.  The cluster is
fault-tolerant: ``engine.fail_node(node_id)`` kills a node mid-serve
(keys re-route to ring successors, heals restore replication), TTLs
expire stale copies lazily at lookup, and the delayed write-on-miss
re-admits a missed prefix only once its fallback prefill produced the
first token (`notify_recompute_done`).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.adaptive import DecodeTable
from repro.core.chunks import KVManifest
from repro.core.codec import KVCodec
from repro.core.fetch import (FetchPlan, PlannedChunk, build_plan,
                              sharded_layers_ready, split_plan_shards)
from repro.core.fetch_controller import (ActiveFetch, FetchController,
                                         FetchHooks, PipelineConfig)
from repro.core.layout import IntraLayout
from repro.core.scheduler import FetchingAwareScheduler, ReqState, Request
from repro.cluster.costmodel import CHIPS, EngineCostModel
from repro.cluster.decodepool import DecodePool
from repro.cluster.network import LossModel, make_link
from repro.cluster.storage import StorageCluster
from repro.models.attention import attend
from repro.models.common import rms_norm
from repro.models.transformer import lm_logits
from repro.paged.cache import PagedKVCache
from repro.serving import paged_model


# Shadow rids for mesh-sharded fetches live far above any real rid so
# the per-shard controller flows can never collide with request flows.
_SHADOW_RID_BASE = 10_000_000


@dataclasses.dataclass
class EngineStats:
    restore_buffer_high_water: int = 0
    restored_tokens: int = 0
    fetched_bytes: int = 0
    prefill_stall_time: float = 0.0  # virtual time spent waiting for KV


class _EngineHooks(FetchHooks):
    """Real codec restoration driven by the controller's restore events."""

    def __init__(self, engine: "LiveEngine"):
        self.engine = engine

    def restore_seconds(self, fetch: ActiveFetch, pc: PlannedChunk) -> float:
        return 0.002  # frame-wise restoration cost (matches the simulator)

    def on_restored(self, fetch: ActiveFetch, pc: PlannedChunk,
                    now: float) -> None:
        self.engine._restore_chunk(fetch.req, fetch.plan, pc)

    def comp_times(self, req: Request):
        eng = self.engine
        if eng.cost is None:
            return None
        suffix = max(req.prompt_len - req.reuse_tokens, 1)
        return eng.cost.layer_comp_times(suffix)


class LiveEngine:
    """Single-node engine over a reduced dense model (real compute)."""

    # ``store`` is a flat KVStore (single implicit node, unbounded) or a
    # multi-node StorageCluster (capacity-bounded eviction, placement,
    # longest-prefix-match partial hits — see docs/storage_tier.md).
    def __init__(self, params, cfg: ModelConfig, store, *,
                 n_pages: int = 256, page_size: int = 16,
                 policy: str = "kvfetcher", max_running: int = 4,
                 resolution: str = "240p",
                 fetch_mode: str = "sync",
                 bandwidth=None,
                 loss: Optional[LossModel] = None,
                 link_policy: Optional[str] = None,  # None -> "fair"
                 link_ramp: Optional[str] = None,  # None -> "instant"
                 rto_mode: str = "adaptive",  # or "fixed" (baseline)
                 use_table_sizes: bool = False,  # model Appx A.2 sizes
                 # ABR selection: None keeps the legacy rule (adaptive
                 # iff a decode table is given); False pins
                 # ``resolution`` even with a table (the fixed-res
                 # baseline the ttft.abr.* rows compare against)
                 adaptive: Optional[bool] = None,
                 # ladder the selector may pick from (None = the full
                 # RESOLUTION_ORDER; narrow it to the registered
                 # manifest ladder for cross-env determinism tests)
                 resolutions: Optional[Tuple[str, ...]] = None,
                 decode_table: Optional[DecodeTable] = None,
                 cost: Optional[EngineCostModel] = None,
                 # speculative prefetch + host staging tier: a
                 # repro.cluster.staging.PrefetchManager over `store`
                 prefetch=None,
                 # user-level fair scheduling: a
                 # repro.cluster.fairness.FairScheduler shared with the
                 # FetchingAwareScheduler (docs/fairness.md); submit()
                 # carries user=/slo_tier= per request
                 fairness=None,
                 # fleet mode (docs/fleet.md): the fleet harness drains
                 # the shared fair backlog centrally and hands ready
                 # fetches to dispatch_fetch(); step() must not race it
                 external_dispatch: bool = False,
                 # streaming client view: called as on_token(req, token,
                 # t) the moment each token exists — first token inside
                 # prefill, then once per decode step.  ``t`` is the
                 # engine clock (virtual under a bandwidth trace), so a
                 # client callback sees the same TTFT/inter-token gaps
                 # the metrics report
                 on_token: Optional[Callable[[Request, int, float],
                                             None]] = None,
                 # logits sink for verification: called as
                 # on_logits(req, logits) with a float32 [V] host copy of
                 # the logits behind each token; the engine keeps none,
                 # and copies [B, V] off the device only when it is set
                 on_logits: Optional[Callable[[Request, np.ndarray],
                                              None]] = None,
                 # shard the paged cache over a jax device mesh
                 # (launch/mesh.py) and run per-shard fetch/decode/
                 # restore plans as independent flows through the one
                 # controller; mesh_shards= overrides the shard count
                 # (e.g. model-parallel degree to emulate on a small
                 # debug mesh)
                 mesh=None, mesh_shards: Optional[int] = None):
        assert fetch_mode in ("sync", "async")
        self.params = params
        self.cfg = cfg
        self.store = store
        self.prefetch = prefetch
        if prefetch is not None:
            assert isinstance(store, StorageCluster), \
                "prefetch= needs a multi-node StorageCluster store"
        # pages hold KV in the weights' dtype (bf16 weights -> bf16 KV)
        self.cache = PagedKVCache(cfg, n_pages, page_size,
                                  dtype=params["embed"].dtype)
        self.external_dispatch = external_dispatch
        # mesh sharding: page arrays live distributed over the mesh's
        # "model" axis (kv heads); fetch plans split into per-shard
        # subplans so each shard restores its slice as its own flow
        self.n_shards = 1
        if mesh is not None or mesh_shards is not None:
            self.n_shards = int(mesh_shards) if mesh_shards is not None \
                else dict(mesh.shape).get("model", 1)
            assert self.n_shards >= 1
            if mesh is not None:
                self.cache.shard(mesh)
        #: rid -> (req, shard subplans) for fetches in sharded flight
        self._sharded: Dict[int, Tuple[Request, List[FetchPlan]]] = {}
        #: shadow rid -> real request (restore callbacks remap through it)
        self._shadow_real: Dict[int, Request] = {}
        self.fairness = fairness
        self.sched = FetchingAwareScheduler(policy, max_running=max_running,
                                            fairness=fairness)
        self.resolution = resolution
        self.fetch_mode = fetch_mode
        self.stats = EngineStats()
        self.prompts: Dict[int, np.ndarray] = {}
        self.outputs: Dict[int, List[int]] = {}
        self.finished: List[Request] = []
        self._clock = 0.0
        self.virtual = bandwidth is not None
        assert self.virtual or (fetch_mode == "sync" and loss is None
                                and link_policy is None
                                and link_ramp is None), \
            "WAN options (async fetch, loss=, link_policy=, link_ramp=) " \
            "need a bandwidth trace (virtual clock)"
        self.on_token = on_token
        self.on_logits = on_logits
        self.cost = cost
        self.ctrl: Optional[FetchController] = None
        if isinstance(store, StorageCluster) and (loss is not None
                                                  or link_policy is not None
                                                  or link_ramp is not None):
            assert all(n.link is None for n in store.nodes), \
                "loss=/link_policy=/link_ramp= only shape the default " \
                "link; nodes with their own links must carry their own " \
                "LossModel/policy/ramp: StorageNode(link=make_link(" \
                "trace, policy=, loss=, ramp=))"
        if self.virtual:
            if self.cost is None:
                self.cost = EngineCostModel(cfg, CHIPS["h20"], 1)
            pool = DecodePool(decode_table) if decode_table else None
            # concurrent fetches contend for one WAN link (fair or DRR
            # split, optionally slow-start ramped) and survive seeded
            # chunk loss via adaptive-RTO retransmission — the same link
            # model the simulator pumps
            link = make_link(bandwidth, policy=link_policy, loss=loss,
                             ramp=link_ramp)
            pipe_kw = {}
            if resolutions is not None:
                pipe_kw["resolutions"] = tuple(resolutions)
            self.ctrl = FetchController(
                self.sched, link, table=decode_table, pool=pool,
                config=PipelineConfig(
                    adaptive=(decode_table is not None if adaptive is None
                              else adaptive),
                    fixed_resolution=resolution,
                    pipelined=fetch_mode == "async",
                    layerwise_admission=(fetch_mode == "async"
                                         and policy == "kvfetcher"),
                    use_table_sizes=use_table_sizes,
                    rto_mode=rto_mode, **pipe_kw),
                hooks=_EngineHooks(self), prefetcher=prefetch)
            if isinstance(store, StorageCluster):
                # heal="link" re-replication transfers share the
                # controller's virtual clock + the nodes' links
                store.bind(self.ctrl.push_event)
                self.ctrl.rtt_sink = store.observe_rtt
                # per-resolution usage feedback for rung-level eviction
                self.ctrl.res_sink = store.note_resolution_use
            if prefetch is not None:
                prefetch.bind(self.ctrl.push_event)
        elif prefetch is not None:
            # wall clock has no event queue to stream speculation on
            assert prefetch.transport == "sync", \
                "wall-clock engines need PrefetchManager(transport='sync')"

    # -- time: virtual clock in modeled-network mode, else wall clock -------
    def now(self) -> float:
        # wall-clock mode is the integration-test default (fetches
        # complete synchronously at dispatch); every replayed event log
        # comes from virtual-clock mode, where this branch never runs
        return self._clock if self.virtual \
            else time.monotonic()  # repro-lint: allow(no-wall-clock)

    # -- storage-node churn ---------------------------------------------------
    def fail_node(self, node_id: str) -> None:
        """Kill one storage node at the engine's current clock: its keys
        re-route to ring successors and the cluster's heal queue
        restores the replication factor (`docs/storage_tier.md`).
        Subsequent lookups for prefixes it alone held miss and fall back
        to full prefill until healed."""
        assert isinstance(self.store, StorageCluster), \
            "fail_node needs a multi-node StorageCluster store"
        self.store.fail_node(node_id, self.now())

    def recover_node(self, node_id: str) -> None:
        assert isinstance(self.store, StorageCluster)
        self.store.recover_node(node_id, self.now())

    # -- intake -------------------------------------------------------------
    def submit(self, tokens: np.ndarray, reuse_prefix: Optional[str] = None,
               reuse_tokens: int = 0, max_new_tokens: int = 8,
               user: Optional[str] = None,
               slo_tier: Optional[str] = None,
               rid: Optional[int] = None) -> Request:
        # fleet harnesses pass fleet-global rids so one placement log
        # covers every engine; standalone use keeps the local counter
        rid = len(self.prompts) if rid is None else int(rid)
        assert rid not in self.prompts, f"rid {rid} already submitted"
        req = Request(rid=rid, arrival=self.now(), prompt_len=len(tokens),
                      max_new_tokens=max_new_tokens,
                      reuse_tokens=reuse_tokens, prefix=reuse_prefix,
                      user=user, slo_tier=slo_tier)
        self.prompts[rid] = np.asarray(tokens)
        self.outputs[rid] = []
        self.sched.submit(req, req.arrival)
        return req

    # -- fetch dispatch -------------------------------------------------------
    def dispatch_fetch(self, req: Request) -> None:
        """External-dispatch entry point: the fleet harness drained the
        shared fair backlog and placed ``req`` here — start its fetch
        and re-run admission, exactly what step() does internally when
        it owns dispatch."""
        self._start_fetch(req)
        self.sched.schedule(self.now())

    def local_restore(self, req: Request) -> None:
        """Serve ``req`` from this serving node's own resident KV: a
        real restore from the cataloged manifest with ZERO virtual
        network time (the bytes never cross the wire — the affinity
        router already put the request where its prefix lives).
        Fairness sees the same 0-byte "fetched" event the simulator
        logs for a local hit."""
        assert isinstance(self.store, StorageCluster) and req.prefix
        entry = self.store.catalog[req.prefix]
        plan = build_plan(req.rid, entry.manifest)
        self.cache.add_seq(req.rid, req.prompt_len + req.max_new_tokens)
        self._run_fetch_wall(req, plan)

    def _start_fetch(self, req: Request) -> None:
        """Resolve the request's prefix against the store and start the
        fetch.  Against a multi-node `StorageCluster` the resolution is a
        longest-prefix-match over the prompt tokens: a **full** hit
        fetches the whole ask, a **partial** hit fetches the resident
        *ancestor* manifest (the tail becomes extra suffix prefill — same
        tokens, just more compute), and a **miss** falls back to a plain
        full prefill; fetches route over the serving node's own link."""
        with jax.profiler.TraceAnnotation("kvf.fetch.start", rid=req.rid):
            link = None
            res_avail = None
            served_key = None
            if isinstance(self.store, StorageCluster):
                tokens = self.prompts[req.rid][:req.reuse_tokens]
                staged = (self.prefetch.host_lookup_tokens(tokens, self.now())
                          if self.prefetch is not None else None)
                if staged is not None:
                    # host-first: the speculatively staged copy serves from
                    # host DRAM over the staging tier's h2d link — the WAN
                    # is off this request's TTFT path entirely
                    req.storage_hit = "host"
                    req.storage_node = "host"
                    req.prefix = staged.key
                    self.prefetch.observe(staged.key, self.now())
                    man = staged.manifest
                    link = self.prefetch.staging.link
                else:
                    hit = self.store.lookup_tokens(tokens, self.now())
                    if self.prefetch is not None:
                        self.prefetch.observe(
                            hit.entry.key if hit.entry is not None
                            else hit.missed_key, self.now())
                    req.storage_hit = hit.kind
                    if hit.kind == "miss":
                        req.storage_miss_key = hit.missed_key
                        self.sched.notify_fetch_miss(req, self.now())
                        return
                    req.storage_node = hit.node.node_id
                    if hit.kind == "partial":
                        req.requested_reuse_tokens = req.reuse_tokens
                        req.reuse_tokens = hit.covered_tokens
                        req.prefix = hit.entry.key  # fetch the ancestor
                    man = hit.entry.manifest
                    link = hit.node.link
                    res_avail = hit.resolutions
                    served_key = hit.entry.key
            else:
                man = self.store.lookup(req.prefix)
            assert man is not None, f"prefix {req.prefix} not registered"
            plan = build_plan(req.rid, man)
            self.cache.add_seq(req.rid, req.prompt_len + req.max_new_tokens)
            if self.ctrl is None:
                self._run_fetch_wall(req, plan)
                return
            if self.n_shards > 1:
                self._start_sharded(req, plan, link=link,
                                    resolutions=res_avail,
                                    served_key=served_key)
                return
            self.ctrl.start(req, plan, self.now(), link=link,
                            resolutions=res_avail, served_key=served_key)
            if self.fetch_mode == "sync":
                # blocking baseline: the engine idles until the (serialized)
                # pipeline finishes; the virtual clock absorbs the whole fetch
                self._clock = max(self._clock, self.ctrl.drain(plan))

    # -- mesh-sharded fetch: per-shard plans as independent flows -------------
    def _start_sharded(self, req: Request, plan: FetchPlan, *,
                       link=None, resolutions=None,
                       served_key=None) -> None:
        """Split the plan by layer-group shard and run every shard's
        fetch/decode/restore stream as its own flow through the ONE
        controller event loop: shards contend on the link like the real
        per-device DMA streams would, and the request is admitted when
        `sharded_layers_ready` over the subplans says its contiguous
        layer prefix landed.  Each shard fetches under a *shadow* of
        the request (fresh rid, state=WAITING) so the controller's
        per-shard completion bookkeeping — fairness charge, scheduler
        notify, early admission — all no-op; the REAL request completes
        exactly once, in `_check_sharded`, when the last shard drains."""
        subplans = split_plan_shards(plan, self.n_shards)
        self._sharded[req.rid] = (req, subplans)
        req.fetch_started = self.now()
        for s, sp in enumerate(subplans):
            shadow = dataclasses.replace(
                req, rid=_SHADOW_RID_BASE + req.rid * 64 + s,
                token_times=[])
            # replace() copied WAITING_FOR_KV; shadows must stay inert
            # for the scheduler (see notify_fetch_done / early admit)
            shadow.state = ReqState.WAITING
            self._shadow_real[shadow.rid] = req
            sp.rid = shadow.rid
            self.ctrl.start(shadow, sp, self.now(), link=link,
                            resolutions=resolutions,
                            served_key=served_key)
        if self.fetch_mode == "sync":
            t = self._clock
            for sp in subplans:
                t = max(t, self.ctrl.drain(sp))
            self._clock = t
            self._check_sharded()

    def _check_sharded(self) -> None:
        """Aggregate per-shard progress into each real request: update
        its ready-layer prefix and fire the single completion (or miss)
        when every shard lands (or any aborts)."""
        for rid in list(self._sharded):
            req, subplans = self._sharded[rid]
            req.layers_ready = sharded_layers_ready(subplans)
            if any(sp.aborted for sp in subplans):
                del self._sharded[rid]
                self.sched.notify_fetch_miss(req, self.now())
            elif all(sp.done for sp in subplans):
                del self._sharded[rid]
                if self.fairness is not None:
                    nbytes = float(sum(
                        pc.sizes.get(pc.resolution or self.resolution, 0)
                        for sp in subplans for pc in sp.chunks))
                    self.fairness.on_fetch_done(req, nbytes)
                self.sched.notify_fetch_done(req, self.now())

    def _run_fetch_wall(self, req: Request, plan: FetchPlan) -> None:
        """Original wall-clock behaviour: fetch synchronously, stamping
        real timestamps (no network model)."""
        req.fetch_started = self.now()
        for pc in plan.chunks:
            pc.resolution = self.resolution
            pc.t_transmit_start = pc.t_transmit_done = self.now()
            self._restore_chunk(req, plan, pc)
            pc.t_decode_done = pc.t_restored = self.now()
        req.layers_ready = plan.layers_ready()
        self.sched.notify_fetch_done(req, self.now())

    # -- frame-wise restoration (real codec + paged scatter) -----------------
    def _restore_chunk(self, req: Request, plan: FetchPlan,
                       pc: PlannedChunk) -> None:
        # sharded fetches restore under shadow requests; the pages
        # belong to the real rid's sequence
        req = self._shadow_real.get(req.rid, req)
        man = plan.manifest
        assert man is not None
        res = pc.resolution or self.resolution
        blob = man.blobs[(pc.ref.chunk_id, res)]
        with jax.profiler.TraceAnnotation(
                "kvf.restore.chunk", rid=req.rid, kind=pc.ref.kind,
                nbytes=len(blob),
                tokens=pc.ref.token_end - pc.ref.token_start):
            self.stats.fetched_bytes += len(blob)
            lay = IntraLayout(self.cfg.num_kv_heads, self.cfg.head_dim,
                              *man.layout)
            codec = KVCodec(self.cfg.num_kv_heads, self.cfg.head_dim, lay)
            scales_all = man.scales[pc.ref.kind]
            for toks, qt in codec.iter_decode_frames(blob):
                buf = qt.nbytes * 2  # residual + reference frame
                self.stats.restore_buffer_high_water = max(
                    self.stats.restore_buffer_high_water, buf)
                global_toks = toks + pc.ref.token_start
                for li, layer in enumerate(pc.ref.layers):
                    q, scales = qt[:, li], scales_all[layer]
                    with jax.profiler.TraceAnnotation(
                            "kvf.restore.h2d",
                            nbytes=q.nbytes + scales.nbytes):
                        q, scales = jnp.asarray(q), jnp.asarray(scales)
                    self.cache.restore_tokens(layer, pc.ref.kind, req.rid,
                                              global_toks, q, scales)
                self.stats.restored_tokens += len(toks)

    # -- prefill -------------------------------------------------------------
    def _prefill(self, req: Request) -> None:
        tokens = self.prompts[req.rid]
        total = len(tokens) + req.max_new_tokens
        if req.rid not in self.cache.seqs:
            self.cache.add_seq(req.rid, total)
        else:
            self.cache.ensure_capacity(req.rid, total)
        if req.needs_fetch:
            logits = self._suffix_prefill(req, tokens)
        else:
            with jax.profiler.TraceAnnotation("kvf.prefill.full",
                                              rid=req.rid,
                                              tokens=len(tokens)):
                logits, kvs = paged_model.prefill_collect_kv(
                    self.params, self.cfg, jnp.asarray(tokens[None]))
                for layer, (k, v) in enumerate(kvs):
                    self.cache.write_prefill(layer, req.rid, k[0], v[0])
            logits = logits[0]
            if self.virtual:
                self._clock += self.cost.prefill_time(len(tokens))
        info = self.cache.seqs[req.rid]
        info.context_len = len(tokens)
        nxt = int(jnp.argmax(logits))
        if self.on_logits is not None:
            self.on_logits(req, np.asarray(logits, np.float32))
        self.outputs[req.rid].append(nxt)
        req.tokens_out = 1
        req.t_first_token = self.now()
        req.token_times.append(req.t_first_token)
        if self.on_token is not None:
            self.on_token(req, nxt, req.t_first_token)
        if (req.storage_hit == "miss" and req.storage_miss_key
                and isinstance(self.store, StorageCluster)):
            # delayed write-on-miss: only now does the recomputed KV
            # exist for the donor to re-upload
            self.store.notify_recompute_done(req.storage_miss_key,
                                             req.t_first_token)

    def _await_layer(self, req: Request, layer: int) -> None:
        """Async mode: block (on the virtual clock) until ``layer``'s
        prefix KV is restored; pipeline stalls are accounted as stall
        time — zero whenever the Appx A.3 condition held at admission."""
        if self.ctrl is None:
            return
        with jax.profiler.TraceAnnotation("kvf.prefill.await", rid=req.rid,
                                          layer=layer):
            while req.fetch_done is None and req.layers_ready <= layer:
                t = self.ctrl.pump_next()
                if self._sharded:
                    self._check_sharded()
                if t is None:
                    if req.fetch_done is not None \
                            or req.layers_ready > layer:
                        break  # the final pump completed a sharded fetch
                    raise RuntimeError(
                        f"rid={req.rid}: layer {layer} KV never arrived")
                if t > self._clock:
                    self.stats.prefill_stall_time += t - self._clock
                    self._clock = t

    def _suffix_prefill(self, req: Request, tokens: np.ndarray) -> jax.Array:
        """Prefill only the non-reused suffix, attending over restored
        prefix KV gathered from the paged cache.  Layer k's compute waits
        for layer k's restore event only (layer-wise pipeline)."""
        with jax.profiler.TraceAnnotation(
                "kvf.prefill.suffix", rid=req.rid,
                tokens=len(tokens) - req.reuse_tokens):
            cfg = self.cfg
            n_pre = req.reuse_tokens
            suffix = jnp.asarray(tokens[None, n_pre:])
            b, s = suffix.shape
            positions = jnp.broadcast_to(
                jnp.arange(n_pre, n_pre + s, dtype=jnp.int32), (b, s))
            pre_pos = jnp.broadcast_to(jnp.arange(n_pre, dtype=jnp.int32),
                                       (b, n_pre))
            info = self.cache.seqs[req.rid]
            bt = np.asarray(info.block_table)
            ps = self.cache.page_size
            rows = bt[np.arange(n_pre) // ps] * ps + np.arange(n_pre) % ps
            comp = (self.cost.layer_comp_times(s) if self.virtual else
                    [0.0] * cfg.num_layers)
            x = self.params["embed"][suffix]
            for i in range(cfg.num_layers):
                self._await_layer(req, i)
                lp = paged_model._layer_params(self.params, cfg, i)
                h = rms_norm(x, lp["ln1"], cfg.norm_eps)
                q, k, v = paged_model._qkv(lp["attn"], h, cfg, positions)
                self.cache.write_prefill(i, req.rid, k[0], v[0],
                                         start_pos=n_pre)
                P = self.cache.n_pages
                pk = self.cache.k_pages[i].reshape(P * ps, cfg.num_kv_heads,
                                                   cfg.head_dim)[rows][None]
                pv = self.cache.v_pages[i].reshape(P * ps, cfg.num_kv_heads,
                                                   cfg.head_dim)[rows][None]
                k_all = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
                v_all = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
                kpos = jnp.concatenate([pre_pos, positions], axis=1)
                out = attend(q, k_all, v_all, positions, kpos, causal=True,
                             window=cfg.sliding_window)
                x = x + jnp.einsum("bshk,hkd->bsd", out, lp["attn"]["wo"])
                h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
                x = x + paged_model._mlp_out(lp, h2, cfg)
                self._clock += comp[i]
            return lm_logits(self.params, cfg, x[:, -1:, :])[0, 0]

    # -- main loop ------------------------------------------------------------
    def step(self) -> bool:
        """One engine iteration. Returns False when idle and done."""
        with jax.profiler.TraceAnnotation("kvf.step"):
            if self.ctrl is not None:
                self.ctrl.pump(self.now())
                if self._sharded:
                    self._check_sharded()
            now = self.now()
            self.sched.schedule(now)
            if not self.external_dispatch:
                for req in self.sched.take_fetches():
                    self._start_fetch(req)
                    self.sched.schedule(self.now())
            if self.prefetch is not None:
                # sglang-style tick: launch speculation for heated
                # prefixes (deferred while demand fetches hold the source
                # link)
                self.prefetch.tick(self.now())
            # newly admitted requests need prefill
            for req in list(self.sched.running):
                if req.t_first_token is None:
                    self._prefill(req)
            # one decode step for every running sequence (continuous
            # batching)
            active = [r for r in self.sched.running
                      if r.tokens_out < r.max_new_tokens]
            if active:
                seq_ids = [r.rid for r in active]
                toks = jnp.asarray([self.outputs[r.rid][-1] for r in active],
                                   jnp.int32)
                positions = jnp.asarray(
                    [len(self.prompts[r.rid]) + r.tokens_out - 1
                     for r in active], jnp.int32)
                with jax.profiler.TraceAnnotation("kvf.decode.step",
                                                  batch=len(active)):
                    logits = paged_model.decode_paged(
                        self.params, self.cfg, toks, positions, self.cache,
                        seq_ids)
                    nxt = np.asarray(jnp.argmax(logits, axis=-1))
                lg = None if self.on_logits is None \
                    else np.asarray(logits, np.float32)
                if self.virtual:
                    ctx = float(np.mean([len(self.prompts[r.rid])
                                         + r.tokens_out for r in active]))
                    self._clock += self.cost.decode_step_time(len(active),
                                                              ctx)
                tnow = self.now()
                for i, req in enumerate(active):
                    if lg is not None:
                        self.on_logits(req, lg[i])
                    self.outputs[req.rid].append(int(nxt[i]))
                    req.tokens_out += 1
                    req.token_times.append(tnow)
                    if self.on_token is not None:
                        self.on_token(req, int(nxt[i]), tnow)
            for req in list(self.sched.running):
                if req.tokens_out >= req.max_new_tokens:
                    self.sched.finish(req, self.now())
                    self.cache.free_seq(req.rid)
                    self.finished.append(req)
            # engine idle but fetches in flight: jump the virtual clock to
            # the next pipeline event so waiting requests make progress
            if (self.virtual and self.ctrl is not None
                    and not self.sched.running and not active):
                t = self.ctrl.next_event_time()
                if t is not None:
                    self._clock = max(self._clock, t)
                    self.ctrl.pump(self._clock)
                    if self._sharded:
                        self._check_sharded()
                    self.sched.schedule(self._clock)
            return bool(self.sched.running or self.sched.waiting
                        or self.sched.waiting_for_kv)

    def run(self, max_steps: int = 1000) -> None:
        for _ in range(max_steps):
            if not self.step():
                break
