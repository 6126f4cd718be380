"""Batched serving runtime: prefill/decode step builders + a paged
continuous-batching scheduler.

serve_step contract (what the dry-run lowers for decode cells): one new
token for every sequence in the batch against a seq_len-deep KV cache,
cache donated, greedy or temperature sampling on-device.

The scheduler serves every tenant through exactly ONE compiled closure:
a fixed (slots, chunk)-window step with per-row valid counts.  Newly
admitted prompts join the running batch as prefill *chunks* — rows mid
prompt consume ``chunk`` tokens per step, decoding rows consume one —
so admission never stalls an in-flight decode step and there is no
per-prompt-length jit cache to explode (the old padded-bucket prefill
machinery is gone).  KV storage is a block-paged pool per lane
(serve/kv_pool.py): fixed-size pages, per-slot page tables, free-list
allocation at admission and reclaim at completion.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import obs
from repro.models.model import Model
from repro.serve.hotswap import HotSwapper, overlap_report
from repro.serve.kv_pool import PagedKVPool, default_pool_pages

_NO_SPAN = contextlib.nullcontext()


def make_prefill_step(model: Model):
    def prefill_step(params, batch, cache):
        logits, cache = model.prefill(params, batch, cache)
        next_tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(
            jnp.int32)
        return next_tok, cache

    return prefill_step


def make_decode_step(model: Model, temperature: float = 0.0):
    def decode_step(params, tokens, cache, key=None):
        logits, cache = model.decode_step(params, tokens, cache)
        lg = logits[:, -1].astype(jnp.float32)
        if temperature > 0.0 and key is not None:
            nxt = jax.random.categorical(key, lg / temperature, axis=-1)
        else:
            nxt = jnp.argmax(lg, axis=-1)
        return nxt[:, None].astype(jnp.int32), cache

    return decode_step


def greedy_generate(model: Model, params, batch, max_new: int,
                    max_len: Optional[int] = None):
    """Jit-friendly generation loop used by examples/serve_batch.py."""
    b = batch["tokens"].shape[0]
    s = batch["tokens"].shape[1]
    max_len = max_len or (s + max_new)
    if model.cfg.family == "encdec":
        cache = model.init_cache(b, max_len, src_len=s)
    else:
        cache = model.init_cache(b, max_len)
    prefill = make_prefill_step(model)
    decode = make_decode_step(model)
    tok, cache = prefill(params, batch, cache)
    toks = [tok]

    def body(carry, _):
        tok, cache = carry
        tok, cache = decode(params, tok, cache)
        return (tok, cache), tok

    (_, _), rest = jax.lax.scan(body, (tok, cache), None,
                                length=max_new - 1)
    return jnp.concatenate([tok[:, None], rest.swapaxes(0, 1)],
                           axis=1)[:, :, 0]


def make_window_step(model: Model, tenant: str = "A") -> Callable:
    """The ONE jitted window closure a scheduler lane runs:
    ``(params, tiles, tokens, cache, m, leak) -> (token, cache)``.

    ``tokens`` is the fixed (width, chunk) window; ``m`` the per-row
    valid counts (chunk tokens for a row mid-prompt, 1 for a decoding
    row, 0 for an empty slot).  The cache fill marker is pinned to
    ``old_len + m`` — pad positions past a row's count are never
    attendable (causal + length masks hit them with exact -1e30s), so
    the emitted token at row position ``m - 1`` is bit-exact with an
    unpadded reference.  Only that position of each row reaches the
    final norm and the head (``width`` head rows, not
    ``width x chunk``); an empty slot's row 0 is computed and ignored.
    Because the compiled shape is prompt-length
    independent, this closure traces exactly once per tenant for ANY
    prompt mix.  The cache (argument 3) is donated.

    ``tiles`` is the tenant's resident planes
    (``CrossbarExecutor.resident_tiles``; ``{}`` on the digital
    backend).  The body reads them under ``tile_scope``, so the
    compiled step carries no tile constants: its program text and its
    device footprint do not grow with the bytes programmed.

    The body runs under ``jax.named_scope("window")``, the argmax under
    ``sample``; the model adds ``attention``, ``mlp`` and ``head`` (the
    row select included).  Scopes name the compiled ops (HLO ``op_name``
    metadata) for the profiler; they change nothing else.

    ``leak`` is the write-plane leakage of an in-flight hot-swap as a
    *traced* scalar: the same compiled step serves leak = 0.0 in steady
    state and the live value during an overlap window — no re-trace at
    window boundaries, and (with ``cfg.use_kernel``) the Pallas kernel
    applies it pre-ADC, so overlap decode never falls back to the
    reference scan.  The digital backend ignores it.
    """
    ex = model.executor
    n_traces = [0]

    def _note_trace():
        # host-side code in a jitted body runs at trace time only: each
        # call here is exactly one (re)trace of THIS closure.  Any trace
        # beyond the first is a re-trace — the runtime counter behind
        # the "zero re-traces at swap-window boundaries" invariant
        # (closure rebuilds at promotion get a fresh counter, so their
        # first trace is expected).
        n_traces[0] += 1
        obs.note_jit_trace("decode", tenant, retrace=n_traces[0] > 1)

    def _window(params, tokens, cache, m):
        with jax.named_scope("window"):
            old = cache["layers"]["len"]                 # (L, B)
            logits, cache = model.decode_step(params, tokens, cache,
                                              last=jnp.maximum(m - 1, 0))
            layers = dict(cache["layers"])
            layers["len"] = (old + m[None, :]).astype(old.dtype)
            with jax.named_scope("sample"):
                tok = jnp.argmax(logits[:, 0].astype(jnp.float32),
                                 axis=-1).astype(jnp.int32)
            return tok, dict(cache, layers=layers)

    def window_step(params, tiles, tokens, cache, m, leak):
        _note_trace()
        if ex is None:
            return _window(params, tokens, cache, m)
        with (ex.read_tenant(tenant), ex.leak_scope(leak),
              ex.tile_scope(tiles)):
            return _window(params, tokens, cache, m)

    return jax.jit(window_step, donate_argnums=(3,))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: jax.Array          # (S,) int32
    max_new: int
    model_id: str = "A"        # tenant whose checkpoint serves this request
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # preemption priority: under pool saturation the scheduler may evict
    # a resident request of strictly lower qos to admit a waiting one
    qos: float = 1.0
    # chunked-prefill progress: prompt tokens already fed to the window
    # closure (scheduler-owned; the first token emits once fed == len)
    fed: int = 0
    # the admission feed (scheduler-owned): prompt tokens, plus — after
    # a preemption — the tokens already emitted, so re-admission replays
    # the whole recomputable state through chunked prefill.  This object
    # IS the host-side spill stub: prompt + out fully determine the
    # greedy continuation, no device state needs saving.
    feed: Optional[np.ndarray] = None
    # times this request was evicted (pages reclaimed) and re-queued
    preemptions: int = 0
    # token positions whose K/V arrived via aliased prefix pages instead
    # of prefill compute (cumulative across re-admissions)
    shared_tokens: int = 0
    # pages the pool allocated at admission (None on the dense path)
    bucket: Optional[int] = None
    # lifecycle timestamps (scheduler tracer clock), filled in by the
    # scheduler when telemetry is on; the span set recorded at completion
    # telescopes exactly: queue_wait [t_submit, t_admit] + prefill
    # [t_admit, t_first] + decode [t_first, t_done] = request wall time
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None


@dataclasses.dataclass
class _Lane:
    """One tenant's serving state: a fixed slot batch against one plane
    set, with its own jitted window closure (``make_window_step``: the
    tenant's planes are an argument of every call, like ``params``)."""
    tenant: str
    params: Any
    slots: List[Optional[Request]]
    cache: Any
    queue: List[Request]
    decode: Callable
    # paged-KV page allocator (None on the dense fallback path)
    pool: Optional[PagedKVPool] = None
    # compiled batch width (fixed at construction: the closure's shape)
    width: int = 0
    # QoS: this lane's *effective* slot quota (admission cap <= width,
    # re-split by set_weights) and its admission weight
    n_slots: int = 0
    weight: float = 1.0
    # served-token accounting (admission + decode tokens), the quantity
    # QoS weights shift; see BatchScheduler.qos_report
    tokens_served: int = 0
    # True while this tenant's own planes are mid-write (in-place swap):
    # its reads pause — admissions hold, in-flight slots freeze — and
    # resume on the promoted weights at the swap boundary
    paused: bool = False
    # modeled per-token device read cost by mode, cached from
    # CrossbarExecutor.device_token_cost at lane build/promotion — the
    # constants the per-token device-time/energy counters accumulate
    device_cost: Optional[Dict[str, Dict[str, float]]] = None


def _split_slots(n_slots: int, weights: Dict[str, float]) -> Dict[str, int]:
    """QoS-weighted budget split across tenant lanes (slots OR pages).

    The budget is ``n_slots`` per tenant (so equal weights reproduce
    the historical even split exactly); quotas are proportional to weight
    with largest-remainder rounding, and a starvation guard pins every
    tenant at >= 1 unit — a resident tenant with queued work always
    decodes, however small its weight.
    """
    total = n_slots * len(weights)
    wsum = float(sum(weights.values()))
    raw = {t: total * float(w) / wsum for t, w in weights.items()}
    alloc = {t: max(1, int(raw[t])) for t in weights}
    leftover = total - sum(alloc.values())
    # hand leftover slots to the largest fractional remainders (name
    # breaks ties, so the split is deterministic)
    order = sorted(weights, key=lambda t: (-(raw[t] - int(raw[t])), t))
    i = 0
    while leftover > 0:
        alloc[order[i % len(order)]] += 1
        leftover -= 1
        i += 1
    while leftover < 0:
        # the >=1 guard oversubscribed the budget: reclaim from the
        # largest allocation that can spare a slot
        t = max(sorted(alloc), key=lambda k: alloc[k])
        if alloc[t] <= 1:
            break   # everyone is at the guard floor; keep the floor
        alloc[t] -= 1
        leftover += 1
    return alloc


class BatchScheduler:
    """Paged continuous-batching scheduler (ragged, multi-tenant).

    Each tenant lane runs ONE jitted window closure of fixed shape
    ``(width, chunk)``: per step, every occupied slot contributes either
    its next ``chunk`` prompt tokens (admission prefill, emitting its
    first token on the final chunk) or one generated token (decode) —
    the per-row valid count ``m`` pins each row's cache fill marker, and
    pad positions are causally masked so the streams are bit-exact with
    an unpadded per-request reference.  Because the compiled shape never
    depends on prompt length, a mixed-length stream costs exactly one
    trace per tenant (the ``serve_jit_retraces_total`` counter pins this
    at runtime) and admissions never stall an in-flight decode step —
    admission is pure host bookkeeping (slot + page-table assignment).

    KV storage defaults to a block-paged pool per lane (``kv="paged"``):
    pages of ``page_size`` tokens, per-slot page tables, refcounted
    free-list allocation at admission and reclaim at completion
    (serve/kv_pool.py).  ``kv="dense"`` keeps the per-slot dense cache —
    same closure, same streams (the bit-exactness oracle the paged
    bench gates against).  Two opt-in paged policies:

    * ``prefix_share=True`` — requests whose feed shares a head with an
      already-prefilled row alias its prefix pages (per-page refcounts,
      copy-on-write when a shared page would take the new row's own
      tokens) and skip the shared prefill positions entirely, so N
      common-head requests peak well below N private copies while
      staying bit-exact with the dense oracle.
    * ``preemption=True`` — under pool/budget saturation a waiting
      request of strictly higher ``Request.qos`` evicts the
      lowest-QoS resident (pages reclaimed, recomputable state spilled
      host-side) instead of FIFO-waiting; the victim re-admits later
      through the ordinary chunked-prefill path and continues its
      stream bit-exactly, with zero drops and zero retraces.

    Passing ``tenants={"A": params_a, "B": params_b, ...}`` multiplexes
    up to ``stack_planes`` checkpoints from the plane bank of ONE
    crossbar executor: each tenant gets its own slot partition, cache,
    pool, and jitted closure (traced under ``executor.read_tenant(t)``
    and fed that tenant's planes as an argument each step), and
    every ``step`` interleaves all token streams.  Requests route by
    ``Request.model_id``.

    A tenant value may also be a ``(params, weight)`` pair: QoS weights
    drive the slot AND page budgets (``_split_slots``: proportional
    quota with a >=1 starvation guard) and the admission order across
    lanes (heavier lanes admit first each step).  Bare params mean
    weight 1.0, which reproduces the historical even split exactly.
    ``set_weights`` re-splits both budgets live at a step boundary.
    """

    def __init__(self, model: Model, params, n_slots: int, max_len: int,
                 tenants: Optional[Dict[str, Any]] = None,
                 mode_policy=None, telemetry: bool = True,
                 kv: str = "paged", page_size: int = 8,
                 kv_pages: Optional[int] = None, chunk: int = 4,
                 prefix_share: bool = False, preemption: bool = False):
        if kv not in ("paged", "dense"):
            raise ValueError(f"kv must be 'paged' or 'dense', got {kv!r}")
        if (prefix_share or preemption) and kv != "paged":
            raise ValueError(
                "prefix_share/preemption operate on the page pool; "
                "they require kv='paged'")
        if kv == "paged" and model.init_paged_cache is None:
            raise ValueError(
                f"model family {model.cfg.family!r} has no paged cache; "
                f"pass kv='dense' (the scheduler targets decoder LMs)")
        if kv == "paged" and max_len % page_size:
            raise ValueError(f"page_size {page_size} must divide max_len "
                             f"{max_len}")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.model = model
        self.n_slots, self.max_len = n_slots, max_len
        self.kv, self.page_size, self.chunk = kv, page_size, int(chunk)
        self.kv_pages = kv_pages
        self.prefix_share = bool(prefix_share)
        self.preemption = bool(preemption)
        self.pages_per_seq = (max_len // page_size if kv == "paged"
                              else 0)
        self.mode_policy = mode_policy
        # per-scheduler telemetry: request lifecycle, window and head
        # fill, QoS shares, modeled (Table-I) array time/energy, and the
        # serve.* profiler spans of step().  Scoped per instance so
        # concurrent schedulers never cross-contaminate and
        # telemetry=False is a clean metrics-off baseline (the CI
        # overhead gate).  Process-wide signals (engine dispatch, jit
        # trace/retrace counters) live in obs.registry() instead.
        self.telemetry = telemetry
        self.metrics = obs.MetricsRegistry(enabled=telemetry)
        self.tracer = obs.Tracer(enabled=telemetry)
        tenant_params: Dict[str, Any] = {}
        self._weights: Dict[str, float] = {}
        for t, spec in (dict(tenants) if tenants else {"A": params}).items():
            if (isinstance(spec, (tuple, list)) and len(spec) == 2
                    and isinstance(spec[1], (int, float))):
                p, w = spec
            else:
                p, w = spec, 1.0
            if w <= 0:
                raise ValueError(
                    f"tenant {t!r} QoS weight must be > 0, got {w}")
            tenant_params[t] = p
            self._weights[t] = float(w)
        if "A" not in tenant_params:
            raise ValueError("tenant 'A' is required (it anchors the "
                             "plane banks)")
        executor = getattr(model, "executor", None)
        if len(tenant_params) > 1 and executor is None:
            raise RuntimeError(
                "multi-tenant multiplexing serves each checkpoint from "
                "one plane of a stacked bank; it requires the "
                "crossbar backend (ModelConfig(backend='crossbar'))")
        if mode_policy is not None and executor is None:
            raise RuntimeError(
                "mode_policy selects per-weight crossbar read modes; it "
                "requires the crossbar backend "
                "(ModelConfig(backend='crossbar'))")
        if executor is not None:
            # crossbar backend: program each tenant's weights onto its
            # plane set ONCE at scheduler construction — the jitted
            # window closures below trace against already-programmed
            # tiles (program-at-load, read-at-inference).  mode_policy
            # decides each weight's plane layout here, at program time;
            # the closures then dispatch per weight with no extra traces
            # (expansion-fused reads are leak-free constants, deep-net
            # reads keep the traced leak operand)
            for t in sorted(tenant_params):
                with executor.read_tenant(t):
                    executor.ensure_programmed(tenant_params[t],
                                               mode_policy=mode_policy)
        self._slot_quota = _split_slots(n_slots, self._weights)
        self._page_quota: Dict[str, int] = {}
        if kv == "paged":
            if kv_pages is None:
                self._page_quota = {
                    t: self._slot_quota[t] * self.pages_per_seq
                    for t in self._weights}
            else:
                self._page_quota = _split_slots(kv_pages, self._weights)
        self._lanes: Dict[str, _Lane] = {
            t: self._make_lane(t, p) for t, p in sorted(tenant_params.items())}
        self._swap: Optional[HotSwapper] = None
        self._swap_t0: Optional[float] = None
        self.swap_history: List[Dict[str, Any]] = []
        for t, lane in self._lanes.items():
            self._set_qos_gauges(t, lane)

    # -- telemetry helpers ---------------------------------------------------

    def _set_qos_gauges(self, tenant: str, lane: _Lane) -> None:
        self.metrics.gauge(
            "serve_qos_weight",
            help="configured QoS weight per tenant lane").set(
                lane.weight, tenant=tenant)
        self.metrics.gauge(
            "serve_qos_slot_quota",
            help="decode slots the QoS-weighted split granted").set(
                lane.n_slots, tenant=tenant)
        if lane.pool is not None:
            self.metrics.gauge(
                "serve_qos_page_budget",
                help="KV pages the QoS-weighted split granted").set(
                    lane.pool.budget, tenant=tenant)

    def _account_tokens(self, lane: _Lane, n: int, kind: str) -> None:
        """Count ``n`` emitted tokens on a lane: the QoS served-token
        figure, plus modeled device-read time and energy split by read
        mode (Table-I constants via ``device_token_cost``)."""
        if n <= 0:
            return
        lane.tokens_served += n
        if not self.metrics.enabled:
            return
        self.metrics.counter(
            "serve_tokens_total",
            help="tokens emitted, by tenant and kind "
                 "(admission|decode)").inc(n, tenant=lane.tenant, kind=kind)
        if lane.device_cost:
            for mode, c in lane.device_cost.items():
                self.metrics.counter(
                    "serve_device_read_seconds_total",
                    help="modeled device read time spent producing "
                         "tokens, by read mode (t_read accounting)").inc(
                    n * c["read_s"], tenant=lane.tenant, mode=mode)
                self.metrics.counter(
                    "serve_device_energy_joules_total",
                    help="modeled worst-case analog read energy spent "
                         "producing tokens, by read mode").inc(
                    n * c["energy_j"], tenant=lane.tenant, mode=mode)

    def _finish_request(self, lane: _Lane, req: Request) -> None:
        """Completion bookkeeping: counter + the request's span set."""
        req.done = True
        self.metrics.counter(
            "serve_requests_completed_total",
            help="requests that emitted their full max_new budget").inc(
                tenant=lane.tenant)
        tr = self.tracer
        if not tr.enabled or req.t_submit is None:
            return
        tr.record("queue_wait", req.t_submit, req.t_admit,
                  rid=req.rid, tenant=lane.tenant)
        tr.record("prefill", req.t_admit, req.t_first,
                  rid=req.rid, tenant=lane.tenant, bucket=req.bucket)
        tr.record("decode", req.t_first, req.t_done,
                  rid=req.rid, tenant=lane.tenant, n_tokens=len(req.out))
        tr.record("request", req.t_submit, req.t_done,
                  rid=req.rid, tenant=lane.tenant, bucket=req.bucket,
                  n_tokens=len(req.out),
                  ttft_s=req.t_first - req.t_submit)

    # -- lanes ---------------------------------------------------------------

    def _make_lane(self, tenant: str, params) -> _Lane:
        n = self._slot_quota.get(tenant, self.n_slots)
        ex = self.model.executor
        pool = None
        if self.kv == "paged":
            n_pages = self._page_quota.get(
                tenant, default_pool_pages(n, self.max_len, self.page_size))
            pool = PagedKVPool(n_pages, self.page_size, self.max_len, n)
            cache = self.model.init_paged_cache(n, self.max_len, n_pages,
                                                self.page_size)
        else:
            cache = self.model.init_cache(n, self.max_len)
        return _Lane(tenant=tenant, params=params,
                     slots=[None] * n, cache=cache,
                     queue=[], decode=make_window_step(self.model, tenant),
                     pool=pool, width=n, n_slots=n,
                     weight=self._weights.get(tenant, 1.0),
                     device_cost=(ex.device_token_cost(tenant)
                                  if ex is not None else None))

    def _lane_order(self) -> List[str]:
        """QoS admission/decode order: heavier lanes first, name breaks
        ties (so the equal-weight order is the historical sorted one)."""
        return sorted(self._lanes,
                      key=lambda t: (-self._lanes[t].weight, t))

    @property
    def params(self):
        """Tenant A's serving params (single-tenant compatibility)."""
        return self._lanes["A"].params

    @property
    def tenants(self) -> List[str]:
        return sorted(self._lanes)

    @property
    def queue(self) -> List[Request]:
        """Tenant A's queue (single-tenant compatibility)."""
        return self._lanes["A"].queue

    def submit(self, req: Request):
        lane = self._lanes.get(req.model_id)
        if lane is None:
            raise ValueError(
                f"request {req.rid} routes to unknown tenant "
                f"{req.model_id!r}; serving {self.tenants}")
        req.t_submit = self.tracer.now()
        self.metrics.counter(
            "serve_requests_submitted_total",
            help="requests accepted into a tenant queue").inc(
                tenant=lane.tenant)
        lane.queue.append(req)

    # -- dynamic QoS ---------------------------------------------------------

    def set_weights(self, weights: Dict[str, float]) -> None:
        """Re-weight QoS live: recompute the slot quotas and page
        budgets at a step boundary and update the ``serve_qos_*``
        gauges.  ``weights`` may cover any subset of resident tenants;
        the rest keep their current weight.

        Quota growth is capped at each lane's compiled width (resizing
        the batch would force a re-trace and drop in-flight cache
        state); shrinking takes effect as admissions — occupied slots
        above the new quota drain naturally as requests complete.
        Page-budget shrinks likewise only gate NEW admissions.
        """
        for t, w in weights.items():
            if t not in self._lanes:
                raise KeyError(f"no lane for tenant {t!r}: this "
                               f"scheduler serves {self.tenants}")
            if w <= 0:
                raise ValueError(
                    f"tenant {t!r} QoS weight must be > 0, got {w}")
        self._weights.update({t: float(w) for t, w in weights.items()})
        quota = _split_slots(self.n_slots, self._weights)
        pquota = (_split_slots(self.kv_pages, self._weights)
                  if (self.kv == "paged" and self.kv_pages is not None)
                  else None)
        for t, lane in self._lanes.items():
            lane.weight = self._weights[t]
            lane.n_slots = min(quota.get(t, lane.width), lane.width)
            self._slot_quota[t] = lane.n_slots
            if pquota is not None and lane.pool is not None:
                lane.pool.set_budget(pquota[t])
            self._set_qos_gauges(t, lane)

    # -- deep-net-mode hot-swap (serve reads while shadow planes program) ----

    def begin_hot_swap(self, new_params, chunks_per_step: int = 8,
                       tenant: str = "A") -> HotSwapper:
        """Start programming ``new_params`` onto a write plane set.

        Chunks are written between decode steps (inside :meth:`step`);
        when every chunk lands, the planes land atomically at a step
        boundary and subsequent tokens come from the new weights — no
        request is dropped and no decode step reads mixed planes.

        ``tenant`` may name any tenant of the plane bank; the lifecycle
        is chosen by bank state (see ``CrossbarExecutor.begin_swap``).
        With a free plane the swap is *staged*: the tenant — resident or
        a first-time live deploy — keeps serving through the whole
        window and no lane pauses.  With a full bank a non-anchor
        tenant is rewritten *in place*: its lane pauses for the write
        window (its planes are the write target) while every other
        tenant's traffic flows uninterrupted — the same
        read-under-write overlap, re-purposed for multi-tenancy.  A
        paused lane's in-flight requests freeze in place and resume on
        the promoted weights, exactly like single-tenant requests that
        span a flip.
        """
        if self.model.executor is None:
            raise RuntimeError("hot-swap requires the crossbar backend "
                               "(ModelConfig(backend='crossbar'))")
        if self._swap is not None:
            raise RuntimeError("a hot-swap is already in flight")
        self._swap = HotSwapper(self.model.executor, new_params,
                                chunks_per_step=chunks_per_step,
                                tenant=tenant)
        self._swap_t0 = self.tracer.now()
        lane = self._lanes.get(tenant)
        if lane is not None and self._swap.plan.in_place:
            lane.paused = True
        return self._swap

    @property
    def swap_in_flight(self) -> bool:
        return self._swap is not None

    def _apply_promotion(self, tenant: str, new_params) -> None:
        """Land promoted params on a lane: the tenant's window closure
        rebuilds (one fresh trace, zero dropped requests) and its next
        step reads the promoted planes as arguments.  A tenant
        deployed live via ``begin_hot_swap(..., tenant=...)`` gets a
        fresh lane here and starts admitting."""
        lane = self._lanes.get(tenant)
        if lane is None:
            if tenant not in self._weights:
                # a live-deployed tenant joins the QoS split at weight
                # 1.0: its quota comes from the same proportional rule
                # the construction-time split used (existing lanes keep
                # their quotas — resizing them would drop in-flight
                # cache state), so a weight-1.0 newcomer decodes like
                # any other weight-1.0 lane, not at the full base width
                self._weights[tenant] = 1.0
                total = self.n_slots * len(self._weights)
                wsum = sum(self._weights.values())
                self._slot_quota[tenant] = max(1, round(total / wsum))
                if self.kv == "paged":
                    if self.kv_pages is None:
                        self._page_quota[tenant] = (
                            self._slot_quota[tenant] * self.pages_per_seq)
                    else:
                        ptotal = self.kv_pages * len(self._weights)
                        self._page_quota[tenant] = max(
                            self.pages_per_seq, round(ptotal / wsum))
            self._lanes[tenant] = self._make_lane(tenant, new_params)
        else:
            lane.params = new_params
            lane.decode = make_window_step(self.model, tenant)
            lane.paused = False
            ex = self.model.executor
            if ex is not None:
                lane.device_cost = ex.device_token_cost(tenant)
        self._set_qos_gauges(tenant, self._lanes[tenant])

    def _note_swap_window(self, tenant: str, lifecycle: str, policy: str,
                          rep: Dict[str, Any]) -> None:
        """Record a completed swap window: one counter bump plus a span
        tagged with its lifecycle (``staged``/``in_place``) and policy
        (``overlapped``/``stop_the_world``)."""
        self.metrics.counter(
            "serve_swap_windows_total",
            help="completed swap windows, by lifecycle and policy").inc(
                tenant=tenant, lifecycle=lifecycle, policy=policy)
        if self._swap_t0 is not None:
            self.tracer.record(
                "swap_window", self._swap_t0, self.tracer.now(),
                tenant=tenant, lifecycle=lifecycle, policy=policy,
                chunks=rep.get("n_chunks"),
                decode_steps_during=rep.get("decode_steps_during_swap"))

    def stop_the_world_swap(self, new_params,
                            tenant: str = "A") -> Dict[str, Any]:
        """Blocking reprogram (the conventional-2-D-array policy): serving
        stalls while every chunk is written, the planes land, and the
        decode step re-traces.  The comparison baseline for the overlapped
        path — same end state, but no tokens flow during the swap.  Like
        the overlapped path, every deploy lands in ``swap_history`` so
        benches and operators see it."""
        if self.model.executor is None:
            raise RuntimeError("hot-swap requires the crossbar backend "
                               "(ModelConfig(backend='crossbar'))")
        if self._swap is not None:
            raise RuntimeError("a hot-swap is already in flight")
        ex = self.model.executor
        t0 = time.perf_counter()
        stats = ex.swap(new_params, tenant=tenant)
        wall = time.perf_counter() - t0
        self._apply_promotion(tenant, new_params)
        rep = overlap_report(ex.cfg, n_grids=ex.n_resident,
                             n_chunks=stats["n_chunks"],
                             batch_size=self.n_slots,
                             decode_steps_during=0, wall_swap_s=wall)
        rep["policy"] = "stop_the_world"
        rep["tenant"] = tenant
        rep["swap_mode"] = stats.get("swap_mode", "staged")
        self.swap_history.append(rep)
        self._swap_t0 = t0
        self._note_swap_window(tenant, rep["swap_mode"],
                               "stop_the_world", rep)
        self._swap_t0 = None
        return stats

    def _advance_swap(self):
        """Program a burst of chunks; promote at the step boundary once
        the staged planes are fully written."""
        sw = self._swap
        if sw is None:
            return
        sw.step()
        if sw.done:
            new_params = sw.promote()
            self._apply_promotion(sw.tenant, new_params)
            rep = sw.report(batch_size=self.n_slots)
            self.swap_history.append(rep)
            self._note_swap_window(sw.tenant, rep["swap_mode"],
                                   "overlapped", rep)
            self._swap = None
            self._swap_t0 = None

    # -- admission (host bookkeeping only: slots + pages) --------------------

    def _leak_now(self) -> jax.Array:
        """The leak scalar this step's closures should carry (see
        ``CrossbarExecutor.current_leak_codes``): 0.0 outside a swap
        window, the write plane's leakage inside one."""
        ex = self.model.executor
        return (ex.current_leak_codes() if ex is not None
                else jnp.float32(0.0))

    def _admit(self, lane: _Lane) -> None:
        """Move queued requests into free slots.

        Pure host bookkeeping — a slot index, a page-table row, a fill
        marker — so admission can NEVER stall an in-flight decode step;
        the admitted prompt streams into the running batch as prefill
        chunks on subsequent :meth:`step` calls.  When the page pool
        (or the QoS page budget) cannot cover a request's whole
        lifetime (``min(prompt + max_new - 1, max_len)`` tokens,
        claimed up front so an admitted request can never deadlock
        mid-decode), the request waits in FIFO order — queued, never
        dropped — unless ``preemption`` is on and a strictly
        lower-QoS resident can be evicted (see :meth:`_preempt_for`).

        With ``prefix_share`` on, the allocation consults the pool's
        prefix index: pages whose token chain matches the head of this
        request's feed are aliased (refcounted) instead of freshly
        claimed, the fill marker and prefill cursor start past the
        shared positions (their K/V is already written), and any
        partially-covered aliased page is privatized copy-on-write —
        the device page copy happens here, before the row's first
        write.  The admitted streams stay bit-exact with the dense
        oracle because a chain hit pins page contents byte-for-byte.
        """
        while lane.queue:
            req = lane.queue[0]
            plen = int(req.prompt.shape[0])
            if plen - 1 >= self.max_len:
                # the last real token's K/V lands at position plen - 1:
                # the prompt must fit strictly inside the cache depth or
                # the write (and every token after it) silently falls
                # off the end
                raise ValueError(f"prompt length {plen} exceeds the "
                                 f"scheduler's max_len {self.max_len}")
            free = [i for i, s in enumerate(lane.slots) if s is None]
            active = sum(s is not None for s in lane.slots)
            if active >= lane.n_slots or not free:
                if not (self.preemption and self._preempt_for(lane, req)):
                    return
                continue
            row = free[0]
            # the feed replays prompt + (after a preemption) the tokens
            # already emitted — greedy decode is deterministic, so the
            # re-prefilled row continues its stream bit-exactly
            feed = np.asarray(req.prompt, np.int32)
            if req.out:
                feed = np.concatenate(
                    [feed, np.asarray(req.out, np.int32)])
            shared = 0
            cow_pairs: List[Any] = []
            if lane.pool is not None:
                need = min(plen + req.max_new - 1, self.max_len)
                if self.prefix_share:
                    if not lane.pool.can_alloc_shared(need, feed):
                        if not (self.preemption
                                and self._preempt_for(lane, req)):
                            return        # backpressure: wait, FIFO
                        continue
                    pages, shared, cow_pairs = lane.pool.alloc_shared(
                        row, need, feed)
                else:
                    if not lane.pool.can_alloc(need):
                        if not (self.preemption
                                and self._preempt_for(lane, req)):
                            return        # backpressure: wait, FIFO
                        continue
                    pages = lane.pool.alloc(row, need)
                req.bucket = len(pages)
                for src, dst in cow_pairs:
                    lane.cache = self.model.copy_paged_page(
                        lane.cache, src, dst)
            layers = dict(lane.cache["layers"])
            if lane.pool is not None:
                tab = jnp.asarray(lane.pool.table_row(row))
                layers["pt"] = layers["pt"].at[:, row].set(tab[None])
            # shared positions are pre-written: the fill marker starts
            # past them and prefill skips straight to the divergence
            layers["len"] = layers["len"].at[:, row].set(shared)
            lane.cache = dict(lane.cache, layers=layers)
            lane.queue.pop(0)
            req.feed = feed
            req.fed = shared
            req.shared_tokens += shared
            if shared and self.metrics.enabled:
                self.metrics.counter(
                    "serve_kv_pages_shared_total",
                    help="KV pages aliased from the prefix index at "
                         "admission instead of freshly written").inc(
                    lane.pool.row_shared_pages(row), tenant=lane.tenant)
                self.metrics.counter(
                    "serve_kv_shared_tokens_total",
                    help="prefill token positions skipped because their "
                         "K/V arrived via shared pages").inc(
                    shared, tenant=lane.tenant)
            if cow_pairs and self.metrics.enabled:
                self.metrics.counter(
                    "serve_kv_cow_total",
                    help="shared pages privatized copy-on-write at "
                         "admission").inc(len(cow_pairs),
                                          tenant=lane.tenant)
            if req.t_admit is None:
                # first admission only: re-admissions after a preemption
                # keep the original timestamps so the span set still
                # telescopes over the request's real wall time
                req.t_admit = self.tracer.now()
                if self.metrics.enabled and req.t_submit is not None:
                    self.metrics.histogram(
                        "serve_queue_wait_seconds",
                        help="submit-to-admission wait").observe(
                        req.t_admit - req.t_submit, tenant=lane.tenant)
            lane.slots[row] = req

    def _preempt_for(self, lane: _Lane, head: Request) -> bool:
        """Evict one resident request so ``head`` can admit.

        Victims must sit at *strictly* lower ``qos`` than the waiting
        request — strictness keeps equal-priority traffic pure FIFO and
        guarantees preemption chains terminate (each eviction is paid
        for by a strictly higher-QoS admission, so no two requests can
        evict each other forever).  Among candidates the lowest QoS
        goes first; ties evict the least-progressed request (cheapest
        to recompute), then the highest row for determinism.

        Eviction reclaims the victim's pages (refcount-aware: pages it
        shares with other rows survive for them) and spills its
        recomputable state to the host-side stub it already carries —
        the ``Request`` itself, whose prompt + emitted tokens fully
        determine the greedy continuation.  The victim re-queues right
        behind ``head`` and later re-admits through the ordinary
        chunked-prefill path, continuing its stream bit-exactly (and
        through the SAME compiled closure: eviction is host
        bookkeeping, so the retrace count stays zero across the
        preempt/re-admit boundary).
        """
        cands = [(i, r) for i, r in enumerate(lane.slots)
                 if r is not None and r.qos < head.qos]
        if not cands:
            return False
        row, victim = min(
            cands,
            key=lambda ir: (ir[1].qos, ir[1].fed + len(ir[1].out),
                            -ir[0]))
        victim.preemptions += 1
        victim.fed = 0
        victim.feed = None
        self._release_slot(lane, row)
        lane.queue.insert(min(1, len(lane.queue)), victim)
        self.metrics.counter(
            "serve_preemptions_total",
            help="resident requests evicted (pages reclaimed, state "
                 "spilled to host) to admit higher-QoS work").inc(
            tenant=lane.tenant)
        return True

    def _release_slot(self, lane: _Lane, row: int) -> None:
        """Return a completed slot: reclaim its pages and null its
        table row so stale writes land on the null page, never on a
        page the free list may hand to the next admission."""
        lane.slots[row] = None
        layers = dict(lane.cache["layers"])
        if lane.pool is not None:
            lane.pool.free_row(row)
            layers["pt"] = layers["pt"].at[:, row].set(0)
        layers["len"] = layers["len"].at[:, row].set(0)
        lane.cache = dict(lane.cache, layers=layers)

    def _span(self, name: str):
        """A host span in the profiler's trace (``serve.*``), or nothing
        with telemetry off.  Outside a profiler capture a span costs
        about a microsecond."""
        return TraceAnnotation(name) if self.telemetry else _NO_SPAN

    def step(self) -> List[Request]:
        """One window step for every tenant's active slots; returns
        finished requests (across tenants).

        An in-flight hot-swap advances first — plane chunks program
        strictly between decode steps, and promotion happens here at the
        boundary, so every decode call reads one consistent plane set.
        A lane whose planes are the write target stays paused for the
        window; the other tenant's lane decodes through it.

        Each occupied row contributes its next prompt chunk (mid
        prefill; emits its first token when the prompt drains) or its
        last generated token (decode, ``m = 1``).  Empty rows ride along
        at ``m = 0``.  One fixed-shape call serves them all.

        With telemetry on, the call is the host span ``serve.step``, and
        each lane's phases nest in it as ``serve.admit``,
        ``serve.window``, ``serve.dispatch``, ``serve.sync`` and
        ``serve.emit`` (``serve.swap`` first while a swap is in flight);
        see ``docs/OBSERVABILITY.md``.
        """
        with self._span("serve.step"):
            if self._swap is not None:
                with self._span("serve.swap"):
                    self._advance_swap()
            finished: List[Request] = []
            decoded = False
            leak = self._leak_now()
            ex = self.model.executor
            for t in self._lane_order():
                lane = self._lanes[t]
                if lane.paused:
                    continue
                with self._span("serve.admit"):
                    self._admit(lane)
                if all(s is None for s in lane.slots):
                    continue
                with self._span("serve.window"):
                    toks, m, emit, reg_rows = self._window_inputs(lane)
                    # the tenant's read-active planes ride as an argument
                    tiles = ex.resident_tiles(t) if ex is not None else {}
                    toks_dev, m_dev = jnp.asarray(toks), jnp.asarray(m)
                with self._span("serve.dispatch"):
                    tok, lane.cache = lane.decode(lane.params, tiles,
                                                  toks_dev, lane.cache,
                                                  m_dev, leak)
                decoded = True
                with self._span("serve.sync"):
                    tok_host = np.asarray(tok)
                with self._span("serve.emit"):
                    finished += self._emit(lane, emit, reg_rows, tok_host,
                                           int(m.sum()))
            if decoded and self._swap is not None:
                self._swap.note_decode_step()
            return finished

    def _window_inputs(self, lane: _Lane):
        """The lane's (width, chunk) token window and per-row counts
        ``m``, advancing each row's prefill cursor; also which rows emit
        (``"admission"``/``"decode"``) and which finish their prefill."""
        c = self.chunk
        toks = np.zeros((lane.width, c), np.int32)
        m = np.zeros((lane.width,), np.int32)
        emit: List[Optional[str]] = [None] * lane.width
        reg_rows: List[int] = []
        for i, req in enumerate(lane.slots):
            if req is None:
                continue
            feed = req.feed
            flen = int(feed.shape[0])
            if req.fed < flen:
                piece = feed[req.fed:req.fed + c]
                toks[i, :piece.shape[0]] = piece
                m[i] = piece.shape[0]
                req.fed += int(piece.shape[0])
                if req.fed >= flen:
                    # final chunk: the argmax is the request's first
                    # token — or, on a post-preemption re-admission
                    # (out non-empty), the continuation of a stream
                    # that already started
                    emit[i] = "admission" if not req.out else "decode"
                    reg_rows.append(i)
            else:
                toks[i, 0] = req.out[-1]
                m[i] = 1
                emit[i] = "decode"
        return toks, m, emit, reg_rows

    def _emit(self, lane: _Lane, emit: List[Optional[str]],
              reg_rows: List[int], tok_host: np.ndarray,
              useful: int) -> List[Request]:
        """Hand the step's tokens to their requests, finish and release
        completed rows, and count the step; returns the finished
        requests.  ``useful`` is the window positions that fed a real
        token (prompt tokens plus decoding rows)."""
        if self.prefix_share and lane.pool is not None:
            # prefill just completed for these rows: every page
            # wholly covered by the feed is final on device now —
            # index it so later common-head admissions alias it
            for i in reg_rows:
                if lane.slots[i] is not None:
                    lane.pool.register_prefix(
                        i, lane.slots[i].feed.tolist())
        finished: List[Request] = []
        n_admit = n_dec = 0
        for i, req in enumerate(lane.slots):
            if req is None or emit[i] is None:
                continue
            req.out.append(int(tok_host[i]))
            if emit[i] == "admission":
                req.t_first = self.tracer.now()
                n_admit += 1
                if self.metrics.enabled and req.t_submit is not None:
                    self.metrics.histogram(
                        "serve_ttft_seconds",
                        help="submit to first emitted token").observe(
                        req.t_first - req.t_submit, tenant=lane.tenant)
            else:
                n_dec += 1
            if len(req.out) >= req.max_new:
                req.t_done = self.tracer.now()
                self._finish_request(lane, req)
                finished.append(req)
                self._release_slot(lane, i)
        self._account_tokens(lane, n_admit, "admission")
        self._account_tokens(lane, n_dec, "decode")
        if self.metrics.enabled:
            # the window always computes width x chunk positions; the
            # head reads one of them per slot
            computed = lane.width * self.chunk
            pos = self.metrics.counter(
                "serve_window_positions_total",
                help="window positions per step: useful (prompt tokens "
                     "fed plus decoding rows) and computed (width x "
                     "chunk)")
            pos.inc(useful, tenant=lane.tenant, kind="useful")
            pos.inc(computed, tenant=lane.tenant, kind="computed")
            rows = self.metrics.counter(
                "serve_head_rows_total",
                help="head rows per step: emitted (tokens handed out), "
                     "read (one per slot) and computed (the width x "
                     "chunk window positions offered to the head)")
            rows.inc(n_admit + n_dec, tenant=lane.tenant, kind="emitted")
            rows.inc(lane.width, tenant=lane.tenant, kind="read")
            rows.inc(computed, tenant=lane.tenant, kind="computed")
        return finished

    def window_hlo(self, tenant: str = "A") -> str:
        """The compiled HLO text of ``tenant``'s window step, as the
        device runs it.  Its instruction names are the op names a
        profiler trace prints, and each instruction's ``op_name``
        metadata carries the named scopes (``window``, ``attention``,
        ``mlp``, ``head``, ``sample``), so the two together charge
        device time to scopes.  Lowers with the lane's own arguments:
        once the lane has stepped, tracing and compiling both hit JAX's
        caches (no re-trace counted)."""
        lane = self._lanes[tenant]
        ex = self.model.executor
        tiles = ex.resident_tiles(tenant) if ex is not None else {}
        toks = jnp.zeros((lane.width, self.chunk), jnp.int32)
        m = jnp.zeros((lane.width,), jnp.int32)
        return lane.decode.lower(lane.params, tiles, toks, lane.cache, m,
                                 self._leak_now()).compile().as_text()

    def kv_report(self) -> Dict[str, Dict[str, Any]]:
        """Per-tenant page-pool accounting (paged lanes only): sizes,
        live usage, QoS budget, and the conservation invariant
        ``pages_in_use + pages_free == n_pages`` — the paged bench's
        exit gate reads this."""
        return {t: lane.pool.report()
                for t, lane in sorted(self._lanes.items())
                if lane.pool is not None}

    def attn_lane_report(self) -> Dict[str, Any]:
        """Which paged-attention lane the compiled closures dispatch,
        plus the model's streaming configuration.  The dispatch counts
        are the process-global trace-time counters
        (``crossstack_dispatch_total{path=paged_*}``): one bump per
        traced closure, so a serving run whose every closure streamed
        shows ``paged_streamed >= 1`` and no ``paged_scratch`` bump.
        """
        from repro.kernels.paged_attention import paged_path_calls
        cfg = self.model.cfg
        return {"paged_kernel": bool(getattr(cfg, "paged_kernel", False)),
                "stream_min_pages": int(
                    getattr(cfg, "paged_stream_pages", 0)),
                "block_pages": int(getattr(cfg, "paged_block_pages", 16)),
                "pages_per_seq": self.pages_per_seq,
                "dispatch": dict(paged_path_calls)}

    def mode_report(self, tenant: Optional[str] = None) -> Dict[str, Any]:
        """Per-weight read-mode choices and their IR-drop economics for
        a tenant's plane set (``CrossbarExecutor.mode_report``) — the
        operator-facing view of what ``mode_policy`` decided — plus a
        ``traffic`` block turning the static per-mode claims into live
        traffic-weighted figures: tokens served and the modeled device
        read time / energy / pJ-per-token accumulated per read mode.

        ``tenant`` defaults to the scheduler's anchor tenant (the
        executor's first plane, what ``params`` serves); asking for a
        tenant this scheduler has no lane for is a ``KeyError`` naming
        the resident tenants.
        """
        ex = self.model.executor
        if ex is None:
            raise RuntimeError(
                "mode_report requires the crossbar backend "
                "(ModelConfig(backend='crossbar'))")
        if tenant is None:
            tenant = ex.anchor
        lane = self._lanes.get(tenant)
        if lane is None:
            raise KeyError(
                f"no lane for tenant {tenant!r}: this scheduler serves "
                f"tenants {self.tenants}")
        rep = ex.mode_report(tenant=tenant)
        tokens = lane.tokens_served
        modes: Dict[str, Any] = {}
        for mode, cost in sorted((lane.device_cost or {}).items()):
            if self.metrics.enabled:
                read_s = self.metrics.total(
                    "serve_device_read_seconds_total",
                    tenant=tenant, mode=mode)
                energy = self.metrics.total(
                    "serve_device_energy_joules_total",
                    tenant=tenant, mode=mode)
            else:
                # metrics off: the per-token cost is constant, so the
                # accumulated figure is exactly cost * tokens
                read_s = cost["read_s"] * tokens
                energy = cost["energy_j"] * tokens
            modes[mode] = {
                "device_read_s": read_s,
                "energy_j": energy,
                "pj_per_token": (energy / tokens * 1e12
                                 if tokens else 0.0),
            }
        rep["traffic"] = {"tokens_served": tokens, "modes": modes}
        return rep

    def qos_report(self) -> Dict[str, Dict[str, Any]]:
        """Per-tenant QoS accounting in ``swap_history`` style: the
        configured weight, the slot quota the weighted split granted,
        the page budget/usage (paged lanes), and the served-token
        count/share so far (admission + decode tokens) — the figure the
        weights are supposed to shift.

        A view over the scheduler registry when telemetry is on
        (``serve_qos_*`` gauges + ``serve_tokens_total``); the lane
        fields remain authoritative with telemetry off.
        """
        if self.metrics.enabled:
            served = {t: int(self.metrics.total("serve_tokens_total",
                                                tenant=t))
                      for t in self._lanes}
        else:
            served = {t: lane.tokens_served
                      for t, lane in self._lanes.items()}
        total = sum(served.values())
        out = {}
        for t, lane in sorted(self._lanes.items()):
            entry: Dict[str, Any] = {
                "weight": lane.weight,
                "slots": lane.n_slots,
                "tokens_served": served[t],
                "token_share": (served[t] / total if total else 0.0)}
            if lane.pool is not None:
                entry["page_budget"] = lane.pool.budget
                entry["pages_in_use"] = lane.pool.pages_in_use
                entry["pages_owned"] = lane.pool.pages_owned
                entry["pages_shared"] = lane.pool.pages_shared
            out[t] = entry
        return out
