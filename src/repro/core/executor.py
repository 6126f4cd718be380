"""Weight-resident crossbar execution: program at load, read at inference.

The paper's deep-net operating point keeps weights *resident* in the
TiO2/TiO2-x stack — programming happens once when a network is deployed,
and every subsequent inference is a read-only bit-serial MAC against the
already-programmed conductances.  ``engine.linear`` (program-and-run) is
the right op for QAT and fidelity sweeps, but re-quantizing and re-slicing
every weight matrix on every forward call is exactly what a memristor
engine exists to avoid.

``CrossbarExecutor`` is the deployment-side half:

  * :meth:`program_params` walks a model's params pytree once, classifies
    every eligible linear weight (attention projections, dense MLP mats,
    the LM head), and programs each onto cached :class:`ProgrammedLinear`
    tile grids — layer-stacked leaves are unstacked so each layer owns its
    physical tiles.  Re-walking the same tree is a cache hit, never a
    re-program (``stats`` records both).
  * :func:`crossbar_linear` is the drop-in the model zoo routes through:
    inside an :meth:`activate` region it executes ``x @ W`` on the resident
    tiles via ``engine.matmul``; outside (or for weights the executor does
    not hold) it falls back to the caller's digital formulation.

Weight addressing is by *name*: ``models/transformer.py`` pushes name
scopes (``blocks.3.attn``) around each sub-module so the same pure layer
functions resolve their crossbar tiles under jit, where array identity is
meaningless (params are tracers).  The crossbar backend therefore runs the
unrolled layer loop (``scan_layers=False`` path) — layer indices must be
Python ints to name tiles.

Plane-bank residency (PR 5, generalizing PRs 2-3): every resident weight
is a :class:`~repro.core.planes.PlaneBank` — an ordered bank of
``DeviceConfig.stack_planes`` role-tagged plane slots (``free`` /
``staging`` / ``resident(tenant)``).  The executor keeps a single
residency registry over the banks: ``program_params(params, tenant=...)``
deploys any of up to N resident checkpoints (one per plane),
:meth:`residency` reports ``{tenant: fingerprint/version}``, and
``linear(..., tenant=...)`` (or the ambient :meth:`read_tenant` scope a
serving loop jits under) selects the tenant's plane per bank — N models
served from ONE physical stack, the paper's user-reconfigurable stack as
a serving-tier analogue of PUMA's many-workload fabric.

:meth:`begin_swap` targets any tenant with one lifecycle: when a free
plane exists, a **staged** swap reserves it per bank, programs the new
checkpoint in write-latency-costed chunks (:meth:`write_chunks`, meant
to interleave with decode steps), and :meth:`promote` retargets the
tenant's read-enable atomically after verifying per-tile fingerprints —
the tenant serves its old plane through the whole window (zero-downtime
hot-swap, the paper's read-under-write overlap at the serving tier).
When the bank is full, the swap falls back to an **in-place** rewrite of
the tenant's own slot: that tenant's reads pause for the window while
every other resident tenant keeps serving.  With ``stack_planes = 2``
these two configurations are exactly the PR-2 shadow swap and the PR-3
two-tenant multiplex — one code path, not two special cases.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import math
import time
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import engine, ir_drop, planes, timing
from repro.core.engine import EngineConfig
from repro.core.planes import ChunkedProgram, PlaneBank, SwapPlan

#: per-weight read modes a policy may assign ("auto" resolves to one)
READ_MODES = ("expansion", "deepnet")

#: a mode policy: None (= cfg.mode for every weight), a uniform mode,
#: "auto" (IR-drop-aware per-layer selection), or a mapping from weight
#: name / dotted name fragment to a mode (values may themselves be
#: "auto"; the special key "default" covers unmatched weights)
ModePolicy = Union[None, str, Dict[str, str]]

# weight-leaf classification: final path key -> contracted input axes,
# in the context of its parent module key
_ATTN_KEYS = {"wq": 1, "wk": 1, "wv": 1, "wo": 2}
_MLP_KEYS = {"wi": 1, "wg": 1, "wo": 1}
# top-level param stacks whose leading axis is the layer index
_STACKED_ROOTS = ("blocks", "enc_blocks")


def _path_parts(path) -> List[str]:
    out = []
    for k in path:
        out.append(str(getattr(k, "key", getattr(k, "idx",
                                                 getattr(k, "name", k)))))
    return out


def _classify(parts: List[str]) -> Optional[int]:
    """Return contracted-input-axis count for an eligible leaf, else None."""
    if parts == ["head"]:
        return 1
    if len(parts) >= 2:
        mod, leaf = parts[-2], parts[-1]
        if mod == "xattn" and leaf in ("wk", "wv"):
            # cross-attention K/V come from the encoder output via the
            # digital _cross_kv path (model.py); programming them would
            # waste tiles that are never read
            return None
        if mod in ("attn", "xattn") and leaf in _ATTN_KEYS:
            return _ATTN_KEYS[leaf]
        if mod == "mlp" and leaf in _MLP_KEYS:
            return _MLP_KEYS[leaf]
    return None


class CrossbarExecutor:
    """Programs a model's linear weights onto crossbar tiles exactly once
    and serves all subsequent ``x @ W`` reads from the resident tiles."""

    def __init__(self, cfg: EngineConfig = EngineConfig(mode="deepnet")):
        self.cfg = cfg
        self._cache: Dict[str, PlaneBank] = {}
        self._n_in: Dict[str, int] = {}
        # per tenant, the leaf arrays its planes were programmed from:
        # resident conductances are physical state, so serving a DIFFERENT
        # tree through them must be an error, not silent reuse.  Strong
        # refs — identity comparison stays sound (no id() reuse after GC).
        self._programmed_leaves: Dict[str, Tuple[Any, ...]] = {}
        self._swap: Optional[SwapPlan] = None
        self._versions: Dict[str, int] = {}
        # ambient tenant for linear()/fingerprint()/ensure_programmed()
        # when no explicit tenant is passed — trace-time Python state, set
        # by read_tenant() around a serving closure's trace
        self._read_tenant: str = "A"
        # ambient leak override: a serving closure traces under
        # leak_scope(<traced scalar>) so the write-plane leakage is an
        # ARGUMENT of the compiled step (0.0 outside a swap window, the
        # live value inside it) instead of a trace-time constant
        self._leak_override: Optional[Any] = None
        # ambient tile override: a serving closure traces under
        # tile_scope(<its tiles argument>) so the resident planes are
        # ARGUMENTS of the compiled step, not constants baked into it
        self._tile_override: Optional[Dict[str, Any]] = None
        # cached device scalars for current_leak_codes(): cfg is frozen,
        # so both values are constants — one host->device put each, not
        # one per decode step
        self._leak_zero: Optional[jax.Array] = None
        self._leak_live: Optional[jax.Array] = None
        # per-weight read mode (PR 6): mode-variant EngineConfigs are
        # cached so every weight programmed in the same mode shares one
        # frozen cfg (stable jit cache keys — zero re-traces when reads
        # mix modes), plus the resolved policy + reasons for mode_report
        self._mode_cfgs: Dict[str, EngineConfig] = {cfg.mode: cfg}
        self._mode_reasons: Dict[Tuple[str, str], str] = {}
        self._ir_scores: Dict[Tuple[int, int], Dict[str, Any]] = {}
        self.stats = {"programmed": 0, "cache_hits": 0, "program_walks": 0,
                      "swaps": 0, "swap_chunks": 0}
        # wall-clock start of the in-flight swap window, for the
        # executor_swap span recorded at promote()/abort_swap()
        self._swap_t0: Optional[float] = None

    def _event(self, stat: str, metric: str, help: str, n: int = 1,
               **labels: Any) -> None:
        """Bump a legacy ``stats`` entry and its registry counter."""
        self.stats[stat] += n
        obs.registry().counter(metric, help=help).inc(n, **labels)

    # -- tenant addressing ----------------------------------------------------

    @property
    def stack_planes(self) -> int:
        """Bank height N: planes stacked per cell site (and the bound on
        the resident tenant population)."""
        return self.cfg.stack_planes

    @property
    def tenant_names(self):
        """The addressable tenant population, one name per plane slot."""
        return self.cfg.device.tenant_names

    @property
    def anchor(self) -> str:
        """The registry's anchor tenant (first name, "A"): required by
        the serving tier, never evictable, and never paused by an
        in-place rewrite — its deploys must go through staged swaps."""
        return self.tenant_names[0]

    def _check_tenant(self, tenant: str) -> str:
        if tenant not in self.tenant_names:
            raise ValueError(
                f"unknown tenant {tenant!r}: a {self.stack_planes}-plane "
                f"stack serves at most tenants {self.tenant_names}")
        return tenant

    def _resolve_tenant(self, tenant: Optional[str]) -> str:
        return self._check_tenant(tenant or self._read_tenant)

    @contextlib.contextmanager
    def read_tenant(self, tenant: str):
        """Ambient-tenant scope: reads (and eager programming checks)
        inside the block address ``tenant``'s plane set.  Wrap a serving
        closure's trace in this (with :meth:`tile_scope`) so the jitted
        step reads that tenant's tiles from its own arguments."""
        self._check_tenant(tenant)
        prev, self._read_tenant = self._read_tenant, tenant
        try:
            yield self
        finally:
            self._read_tenant = prev

    @property
    def tenants(self) -> List[str]:
        """Resident tenants (those with a programmed plane set)."""
        return sorted(self._programmed_leaves)

    def residency(self) -> Dict[str, Dict[str, Any]]:
        """The unified residency registry: for every resident tenant, the
        checkpoint-content fingerprint its planes were programmed from,
        its monotone deploy version, and its per-mode weight counts
        (``modes``: how many banks serve it from an expansion-fused pair
        vs a deep-net slot) — the one structure dashboards, schedulers
        and swap tooling read instead of poking bank slots."""
        out: Dict[str, Dict[str, Any]] = {}
        for t in self.tenants:
            n_exp = sum(1 for b in self._cache.values()
                        if b.has_tenant(t) and b.is_fused(t))
            n_deep = sum(1 for b in self._cache.values()
                         if b.has_tenant(t)) - n_exp
            out[t] = {"fingerprint": self.fingerprint(tenant=t),
                      "version": self.version(t),
                      "modes": {"expansion": n_exp, "deepnet": n_deep}}
        return out

    def resident_tiles(self, tenant: Optional[str] = None
                       ) -> Dict[str, Any]:
        """The tenant's read-active plane per resident weight, by name:
        the pytree a compiled serving step takes as an argument (see
        :meth:`tile_scope`).  Tenant defaults to the ambient scope."""
        tenant = self._resolve_tenant(tenant)
        return {name: bank.active_for(tenant)
                for name, bank in self._cache.items()
                if bank.has_tenant(tenant)}

    @contextlib.contextmanager
    def tile_scope(self, tiles: Dict[str, Any]):
        """Ambient tile override: reads inside the block take each
        weight's planes from ``tiles`` (a :meth:`resident_tiles` dict)
        instead of the bank.  Trace a serving closure under this with
        the closure's own tiles *argument*: the compiled step then holds
        no tile constants (GBs at published widths) and no second copy
        of the planes in device memory.  Mode and layout still come from
        the bank — they are static Python, fixed at program time."""
        prev, self._tile_override = self._tile_override, tiles
        try:
            yield self
        finally:
            self._tile_override = prev

    # -- write-plane leakage (deep-net overlap reads) ------------------------

    @contextlib.contextmanager
    def leak_scope(self, leak_codes):
        """Ambient leak override: reads inside the block carry
        ``leak_codes`` as their common-mode pre-ADC term, whatever the
        swap state.  Trace a serving closure under this with the
        closure's own *traced* scalar argument — the compiled step then
        accepts the live value per call (0.0 steady-state, the write
        plane's leakage during an overlap window) with zero re-traces."""
        prev, self._leak_override = self._leak_override, leak_codes
        try:
            yield self
        finally:
            self._leak_override = prev

    def current_leak_codes(self) -> jax.Array:
        """The leak value a read issued NOW should carry, as a device
        scalar: the write plane's subthreshold leakage while a swap is in
        flight with ``cfg.swap_leakage`` set, else 0.0.  Serving loops
        feed this to closures traced under :meth:`leak_scope` each step
        (both scalars are cached — no per-step transfer)."""
        if self._swap is not None and self.cfg.swap_leakage:
            if self._leak_live is None:
                self._leak_live = planes.write_leak_scalar(self.cfg)
            return self._leak_live
        if self._leak_zero is None:
            self._leak_zero = jnp.float32(0.0)
        return self._leak_zero

    # -- per-weight read-mode policy (PR 6) ----------------------------------

    def _read_cfg(self, mode: str) -> EngineConfig:
        """The engine config a read in ``mode`` uses: ``self.cfg`` when
        the mode matches, else a cached ``dataclasses.replace`` variant.
        Programming is mode-independent (one ``ProgrammedLinear`` serves
        both read paths), so flipping mode is purely a read-time choice
        of ADC grouping (``rows_per_adc``)."""
        cfg = self._mode_cfgs.get(mode)
        if cfg is None:
            cfg = self._mode_cfgs[mode] = dataclasses.replace(
                self.cfg, mode=mode)
        return cfg

    def _row_tiles(self, k: int) -> int:
        return -(-k // self.cfg.tile_rows)

    def _auto_mode(self, name: str, k: int) -> Tuple[str, str]:
        """IR-drop-aware per-layer selection (ROADMAP item 2).

        Expansion mode cuts worst-case IR deviation (paper: 22%) but
        fuses both planes read-only — no write shadow, so no overlapped
        reprogramming.  The policy therefore spends the fused pairs on
        accuracy-critical layers (attention projections and the LM head,
        where logit fidelity is most sensitive) and keeps the swap-heavy
        MLP mats — the bulk of reprogram chunks — in deep-net layout.
        A layer only qualifies when its row-tiles pair up evenly
        (adjacent row-tiles map onto the two planes; an odd count would
        hit the per-plane ADC fallback and forfeit the IR benefit).
        """
        t = self._row_tiles(k)
        parts = name.split(".")
        critical = name == "head" or "attn" in parts or "xattn" in parts
        if not critical:
            return "deepnet", "auto: swap-heavy (mlp) — keep write shadow"
        if t < 2 or t % 2:
            return ("deepnet",
                    f"auto: {t} row-tile(s) cannot pair across planes")
        return "expansion", "auto: accuracy-critical (attention/head)"

    def _validate_policy(self, policy: ModePolicy) -> None:
        """Reject malformed policies BEFORE any residency state mutates
        — a refused ``program_params`` call must leave the executor
        exactly as it found it."""
        if policy is None:
            return
        valid = READ_MODES + ("auto",)
        if isinstance(policy, str):
            if policy not in valid:
                raise ValueError(
                    f"unknown mode policy {policy!r}: want one of "
                    f"{valid} or a name->mode mapping")
            return
        for pat, mode in policy.items():
            if mode not in valid:
                raise ValueError(
                    f"mode policy entry {pat!r} maps to {mode!r}; want "
                    f"one of {valid}")

    def _resolve_mode(self, policy: ModePolicy, name: str,
                      k: int) -> Tuple[str, str]:
        """(mode, reason) for one weight under ``policy``.

        Mapping keys match the full dotted name, any contiguous dotted
        fragment of it (``"attn"``, ``"attn.wq"``, ``"blocks.0"``; the
        most specific — most segments — wins), or ``"default"`` for the
        rest; values may be ``"auto"``.  Unmatched weights without a
        ``"default"`` entry fall back to deep-net, the swap-capable
        layout.
        """
        if policy is None:
            return self.cfg.mode, "engine default (cfg.mode)"
        if isinstance(policy, str):
            if policy == "auto":
                return self._auto_mode(name, k)
            if policy not in READ_MODES:
                raise ValueError(
                    f"unknown mode policy {policy!r}: want one of "
                    f"{READ_MODES + ('auto',)} or a name->mode mapping")
            return policy, f"uniform policy {policy!r}"
        if name in policy:
            mode, why = policy[name], f"policy[{name!r}]"
        else:
            hay = f".{name}."
            best = None
            for pat in policy:
                if pat != "default" and f".{pat}." in hay:
                    if (best is None
                            or pat.count(".") > best.count(".")
                            or (pat.count(".") == best.count(".")
                                and len(pat) > len(best))):
                        best = pat
            if best is not None:
                mode, why = policy[best], f"policy[{best!r}]"
            else:
                mode, why = policy.get("default", "deepnet"), "policy default"
        if mode == "auto":
            return self._auto_mode(name, k)
        if mode not in READ_MODES:
            raise ValueError(
                f"{name}: mode policy maps to {mode!r}; want one of "
                f"{READ_MODES + ('auto',)}")
        return mode, why

    def mode_for(self, name: str, tenant: Optional[str] = None) -> str:
        """The read mode the named weight is programmed in for a tenant
        (ground truth is bank residency, not the requested policy)."""
        return self._cache[name].mode_for(self._resolve_tenant(tenant))

    def _tile_scores(self, k: int, n: int,
                     max_nodes: int = 1024) -> Dict[str, Any]:
        """Worst-case IR-deviation scores at a weight's tile geometry
        (nodal solves, cached per effective tile)."""
        key = (min(k, self.cfg.tile_rows), min(n, self.cfg.tile_cols))
        score = self._ir_scores.get(key)
        if score is None:
            score = self._ir_scores[key] = ir_drop.mode_ir_report(
                key[0], key[1], r_wire=self.cfg.params.r_wire,
                params=self.cfg.params, max_nodes=max_nodes)
        return score

    def mode_report(self, tenant: Optional[str] = None) -> Dict[str, Any]:
        """Per-weight mode choices with their IR-drop economics.

        For every resident weight of the tenant: the programmed mode,
        why the policy chose it, and the worst-case IR deviation of a
        tile at its geometry under each layout (``ir_drop.mode_ir_report``
        — exact nodal solves at the all-SET/full-drive operating point,
        planar 2n-row tile vs the CrossStack fused pair).  The aggregate
        block carries the mean reduction over expansion-programmed
        layers — the paper's headline 22% figure, asserted >= 20% by
        benchmarks/expansion_bench.py on the paper geometry.
        """
        tenant = self._resolve_tenant(tenant)
        layers: Dict[str, Any] = {}
        for name in sorted(self._cache):
            bank = self._cache[name]
            if not bank.has_tenant(tenant):
                continue
            pw = bank.active_for(tenant)
            score = self._tile_scores(pw.k, pw.n)
            layers[name] = {
                "mode": bank.mode_for(tenant),
                "fused": bank.is_fused(tenant),
                "row_tiles": int(pw.pos.shape[1]),
                "k": pw.k, "n": pw.n,
                "reason": self._mode_reasons.get((tenant, name), ""),
                "dev_deepnet": score["dev_deepnet"],
                "dev_expansion": score["dev_expansion"],
                "ir_drop_reduction": score["ir_drop_reduction"],
            }
        exp = [e for e in layers.values() if e["mode"] == "expansion"]
        agg = {
            "tenant": tenant,
            "n_expansion": len(exp),
            "n_deepnet": len(layers) - len(exp),
            "tile_rows": self.cfg.tile_rows,
            "tile_cols": self.cfg.tile_cols,
            "stack_planes": self.stack_planes,
            # mean worst-case IR-drop reduction the fused pairs buy, over
            # the layers actually programmed in expansion layout
            "ir_drop_reduction_expansion": (
                sum(e["ir_drop_reduction"] for e in exp) / len(exp)
                if exp else 0.0),
        }
        return {"layers": layers, "aggregate": agg}

    def device_token_cost(self, tenant: Optional[str] = None,
                          ) -> Dict[str, Dict[str, float]]:
        """Modeled device cost of ONE full-model read (one token) for a
        tenant, split by read mode — the constants the serving tier's
        per-token device-time/energy counters accumulate.

        Per resident weight (Table-I accounting, ``core/timing.py``):

        * read time: one bit-serial MAC, ``read_time(in_bits)`` — row
          tiles of one slice read concurrently in the device, so depth
          does not multiply time (mode only changes ADC grouping, not
          pulse count);
        * energy: ``in_bits * S * T`` (pulse, slice, row-tile) analog
          column reads, each a worst-case ``mac_energy(R, N_pad)``,
          doubled for the differential pos/neg planes.

        Returns ``{mode: {"grids", "read_s", "energy_j"}}`` with only
        the modes the tenant actually has weights programmed in.
        """
        tenant = self._resolve_tenant(tenant)
        q, p = self.cfg.quant, self.cfg.params
        out: Dict[str, Dict[str, float]] = {}
        for name in sorted(self._cache):
            bank = self._cache[name]
            if not bank.has_tenant(tenant):
                continue
            pw = bank.active_for(tenant)
            s, t, r, n_pad = (int(d) for d in pw.pos.shape)
            mode = bank.mode_for(tenant)
            entry = out.setdefault(
                mode, {"grids": 0.0, "read_s": 0.0, "energy_j": 0.0})
            entry["grids"] += 1
            entry["read_s"] += timing.read_time(q.in_bits, p)
            entry["energy_j"] += (q.in_bits * s * t * 2
                                  * timing.mac_energy(r, n_pad, p=p))
        return out

    # -- programming (the write path; once per deployment) -----------------

    @staticmethod
    def _eligible(leaves) -> List[Tuple[str, Any, int]]:
        """(name, weight, n_in) for every eligible linear leaf, with
        layer-stacked roots unstacked so each layer owns its tiles."""
        out = []
        for path, w in leaves:
            parts = _path_parts(path)
            n_in = _classify(parts)
            if n_in is None:
                continue
            if parts[0] in _STACKED_ROOTS:
                for layer in range(w.shape[0]):
                    name = ".".join([parts[0], str(layer)] + parts[1:])
                    out.append((name, w[layer], n_in))
            else:
                out.append((".".join(parts), w, n_in))
        return out

    def program_params(self, params: Any, tenant: Optional[str] = None,
                       mode_policy: ModePolicy = None) -> int:
        """Program every eligible linear weight in ``params`` onto the
        named tenant's plane set; idempotent per tenant.

        A new tenant claims one free plane slot in every bank — TWO in
        banks where ``mode_policy`` programs the weight in expansion
        layout (the fused pair: both planes RE-high, holding the
        row-tile halves of one doubled-input weight).  ``mode_policy``
        is ``None`` (every weight reads in ``cfg.mode``), a uniform
        ``"expansion"``/``"deepnet"``, ``"auto"`` (IR-drop-aware
        per-layer selection; see :meth:`mode_report`), or a name->mode
        mapping.  Re-walking the same tree is a cache hit — but
        requesting a *different* mode for an already-resident weight is
        an error: modes are physical plane layout, not a read flag.
        Returns the number of weights newly programmed this walk.
        """
        tenant = self._resolve_tenant(tenant)
        self._validate_policy(mode_policy)
        if tenant not in self._programmed_leaves:
            self._require_free_plane(tenant)
        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        if any(isinstance(w, jax.core.Tracer) for _, w in leaves):
            raise TypeError(
                "CrossbarExecutor.program_params needs concrete arrays; "
                "program at load time, before entering jit")
        tree = tuple(w for _, w in leaves)
        if tenant not in self._programmed_leaves:
            self._programmed_leaves[tenant] = tree
        elif not self._same_tree(tree, tenant):
            raise RuntimeError(
                f"tenant {tenant!r} planes are already programmed from a "
                f"different params tree; resident weights are physical "
                f"state — use swap(params, tenant={tenant!r}) / "
                f"begin_swap(params, tenant={tenant!r}) for a "
                f"zero-downtime reprogram")
        self._event("program_walks", "crossstack_program_walks_total",
                    "program_params pytree walks", tenant=tenant)
        new = 0
        for name, w, n_in in self._eligible(leaves):
            if mode_policy is None:
                # no preference: resident weights keep their layout,
                # new ones program in the engine's cfg.mode
                mode, reason = None, "engine default (cfg.mode)"
            else:
                k = math.prod(w.shape[:n_in])
                mode, reason = self._resolve_mode(mode_policy, name, k)
            new += self._program_one(name, w, n_in, tenant, mode, reason)
        if new:
            self._versions[tenant] = self._versions.get(tenant, 0) + 1
        return new

    def _require_free_plane(self, tenant: str) -> None:
        """A first-time tenant needs one free slot per bank.  Resident
        tenants (expansion-fused ones hold TWO slots in their banks), an
        in-flight staged swap's reserved slot, and fused companions all
        occupy planes; admitting a tenant past the bound would either
        overflow the stack or steal the very plane an open swap will
        land on at promote() (making that promotion fail half-applied).
        Bank slot roles are the ground truth once banks exist; before
        any bank does, the tenant count is."""
        staging = self._swap is not None and not self._swap.in_place
        if self._cache:
            if min(b.n_free for b in self._cache.values()) > 0:
                return
        else:
            occupied = len(self._programmed_leaves) + (1 if staging else 0)
            if occupied < self.stack_planes:
                return
        if staging:
            raise RuntimeError(
                f"cannot deploy new tenant {tenant!r} while a hot-swap is "
                f"in flight (the staging plane is the swap's write "
                f"target); promote() or abort_swap() first")
        raise RuntimeError(
            f"stack is full: {self.stack_planes} planes hold resident "
            f"tenants {self.tenants}; evict_tenant() before deploying "
            f"{tenant!r}")

    def _program_one(self, name: str, w: jax.Array, n_in: int,
                     tenant: str, mode: Optional[str], reason: str) -> int:
        bank = self._cache.get(name)
        if bank is not None and bank.has_tenant(tenant):
            have = bank.mode_for(tenant)
            if mode is not None and have != mode:
                raise RuntimeError(
                    f"{name}: tenant {tenant!r} is already resident in "
                    f"{have} layout but the policy asks for {mode}; mode "
                    f"is physical plane layout — evict_tenant() and "
                    f"re-program to change it")
            self._event("cache_hits", "crossstack_program_cache_hits_total",
                        "re-walks that found the weight already resident",
                        tenant=tenant)
            return 0
        k = math.prod(w.shape[:n_in])
        w2d = jnp.asarray(w, jnp.float32).reshape(k, -1)
        if bank is None:
            bank = self._cache[name] = PlaneBank(
                name, n_planes=self.stack_planes)
            self._n_in[name] = n_in
        else:
            ref = bank.any_plane
            if (w2d.shape[0], w2d.shape[1]) != (ref.k, ref.n):
                raise ValueError(
                    f"{name}: tenant {tenant!r} weight shape "
                    f"{w2d.shape} != the bank's tile geometry "
                    f"{(ref.k, ref.n)}; tenants share physical stacks")
        # programming is mode-independent: the same ProgrammedLinear
        # serves both read paths; mode decides slot layout (fused pair
        # vs single plane) and the read-time ADC grouping
        if mode is None:
            mode = self.cfg.mode
        pw = engine.program(w2d, self.cfg)
        fp = planes.fingerprint_weight(w2d)
        if mode == "expansion":
            bank.assign_fused(tenant, pw, fp)
        else:
            bank.assign(tenant, pw, fp)
        self._mode_reasons[(tenant, name)] = reason
        self._event("programmed", "crossstack_programmed_weights_total",
                    "weights programmed onto resident planes",
                    tenant=tenant, mode=mode)
        return 1

    def _same_tree(self, leaves: Tuple[Any, ...], tenant: str) -> bool:
        prog = self._programmed_leaves.get(tenant)
        return (prog is not None and len(prog) == len(leaves)
                and all(a is b for a, b in zip(prog, leaves)))

    def ensure_programmed(self, params: Any,
                          tenant: Optional[str] = None,
                          mode_policy: ModePolicy = None) -> None:
        """Program on the first eager call; afterwards verify the caller is
        serving the SAME params tree the tenant's tiles were programmed
        from.

        Under jit the leaves are tracers and identity CANNOT be verified —
        a caller who programs tree A eagerly and then jit-calls with tree B
        gets tree A's tiles.  The supported flow (BatchScheduler / the
        model's eager entry points) always passes through an eager call,
        where the check is sound.  The tenant defaults to the ambient
        :meth:`read_tenant` scope, so a lane closure jitted under
        ``read_tenant("B")`` checks (and first-programs) tenant B.
        """
        tenant = self._resolve_tenant(tenant)
        leaves = jax.tree_util.tree_leaves(params)
        if any(isinstance(w, jax.core.Tracer) for w in leaves):
            if tenant not in self._programmed_leaves:
                raise RuntimeError(
                    f"tenant {tenant!r} crossbar weights are not "
                    f"programmed and params are tracers; call "
                    f"model.executor.program_params(params, "
                    f"tenant={tenant!r}) eagerly before jitting the "
                    f"serving step")
            return  # tracers: identity unverifiable here (see docstring)
        if self._same_tree(tuple(leaves), tenant):
            return
        # unseen tree: program it (first call), or raise (different tree /
        # a tree extending a manually-programmed subset) via program_params
        self.program_params(params, tenant, mode_policy=mode_policy)

    # -- read path ----------------------------------------------------------

    def has(self, name: str) -> bool:
        return name in self._cache

    def linear(self, x: jax.Array, w: jax.Array, name: str,
               tenant: Optional[str] = None) -> jax.Array:
        """Resident-tile execution of ``x @ W`` for the named weight.

        ``w`` is only consulted for its (static) shape — the arithmetic
        reads the named tenant's plane of the bank (default: the ambient
        :meth:`read_tenant` scope, i.e. tenant "A" unless a serving lane
        set otherwise).  While a hot-swap is in flight and
        ``cfg.swap_leakage`` is set, reads carry the write plane's
        subthreshold leakage; a closure traced under :meth:`leak_scope`
        instead takes the leak as its own traced argument, so a compiled
        serving step applies the LIVE value per call (and the Pallas
        kernel fuses it pre-ADC — overlap reads stay on the kernel
        path).  Reads of a tenant whose own planes are mid-write (an
        in-place tenant swap) are refused — those wordlines are driving
        write pulses, not read pulses.

        Per-weight mode dispatch (PR 6): the read path follows the
        bank's *residency layout* — an expansion-fused pair reads with
        doubled-input ADC grouping through a cached mode-variant cfg, a
        deep-net slot reads as before.  Mode is trace-time Python state
        fixed at program time, so mixed-mode models compile each
        weight's read exactly once; and a fused pair never hosts an
        in-flight write, so its reads carry NO leak term — the leak
        operand keeps flowing to the deep-net weights only, preserving
        the zero-re-trace property at swap-window boundaries.
        """
        tenant = self._resolve_tenant(tenant)
        if (self._swap is not None and self._swap.in_place
                and self._swap.tenant == tenant):
            raise RuntimeError(
                f"tenant {tenant!r} planes are mid-write (in-place swap "
                f"in flight); reads resume after promote()")
        bank = self._cache[name]
        pw = (bank.active_for(tenant) if self._tile_override is None
              else self._tile_override[name])
        mode = bank.mode_for(tenant)
        cfg = self._read_cfg(mode)
        n_in = self._n_in[name]
        lead = x.shape[:-n_in]
        k = math.prod(x.shape[-n_in:])
        if k != pw.k:
            raise ValueError(f"{name}: input dim {k} != programmed {pw.k}")
        if mode == "expansion" and bank.is_fused(tenant):
            # both planes RE-high: the fused pair's shared column never
            # sees a write shadow, so no leakage term — a trace-time
            # constant, not a traced operand (mode is fixed per weight)
            leak = 0.0
        elif self._leak_override is not None:
            leak = self._leak_override
        else:
            leak = (planes.write_leak_codes(cfg)
                    if self._swap is not None and cfg.swap_leakage
                    else 0.0)
        y = engine.matmul(x.reshape(*lead, k).astype(jnp.float32), pw,
                          cfg, leak_codes=leak)
        return y.reshape(*lead, *w.shape[n_in:]).astype(x.dtype)

    # -- fingerprints / versioning -------------------------------------------

    def fingerprint(self, name: Optional[str] = None,
                    tenant: Optional[str] = None) -> str:
        """Digest of the source weights the named tenant's plane(s) were
        programmed (and write-verified) from — checkpoint-content
        addressing, not a raw cell-code hash (``planes.fingerprint_tiles``
        is the tile-state digest write-verify uses).

        With ``name``: the per-tile fingerprint of that weight's plane.
        Without: a combined digest over all resident tiles (sorted by
        name) — two executors serving identical weights agree, and any
        mixed-plane state mid-promotion would produce a digest matching
        neither checkpoint (asserted by the overlap property test).
        Tenant defaults to the ambient :meth:`read_tenant` scope.
        """
        tenant = self._resolve_tenant(tenant)
        if name is not None:
            return self._cache[name].fingerprint_for(tenant)
        h = hashlib.blake2b(digest_size=8)
        for n in sorted(self._cache):
            h.update(n.encode())
            h.update(self._cache[n].fingerprint_for(tenant).encode())
        return h.hexdigest()

    def fingerprints(self, tenant: Optional[str] = None) -> Dict[str, str]:
        """Per-tile fingerprints of the named tenant's plane set."""
        tenant = self._resolve_tenant(tenant)
        return {n: p.fingerprint_for(tenant)
                for n, p in sorted(self._cache.items())}

    def version(self, tenant: str = "A") -> int:
        """Per-tenant monotone deploy counter: 0 = unprogrammed; +1 per
        initial program walk that wrote tiles; +1 per promoted swap."""
        return self._versions.get(self._check_tenant(tenant), 0)

    @property
    def programmed_version(self) -> int:
        """Tenant A's deploy counter (the pre-multiplex quantity, kept so
        existing dashboards stay comparable); see :meth:`version`."""
        return self.version("A")

    # -- deep-net hot-swap (write the shadow planes, then flip) --------------

    @property
    def swap_in_flight(self) -> bool:
        return self._swap is not None

    def begin_swap(self, params: Any, tenant: str = "A") -> SwapPlan:
        """Stage ``params`` for chunked programming of a plane set.

        One lifecycle for every tenant.  When the banks have a free
        plane, the swap is **staged**: a staging slot is reserved per
        bank, the new checkpoint programs into it chunk by chunk, and
        promotion retargets the tenant's read-enable atomically — the
        tenant (resident or a first-time live deploy) never stops
        serving.  When the banks are full, a resident non-anchor tenant
        falls back to an **in-place** rewrite of its own slot: its reads
        pause until :meth:`promote` while every other tenant keeps
        serving (the paper's read-under-write overlap re-purposed for
        multi-tenancy).  The anchor tenant's reads never pause, so its
        swaps require a free plane.

        The incoming tree must carry exactly the resident tile set with
        matching shapes (a new checkpoint, fine-tuned delta, or
        recalibrated conductances — not a different architecture).
        Returns the chunk work-list; drive it with :meth:`write_chunks`
        and finish with :meth:`promote`.

        Expansion-fused weights refuse overlap writes: a fused pair
        holds both of its planes RE-high for the tenant's reads, so
        there is no write shadow to stage into — the paper's IR-drop
        win trades away read-under-write.  A tenant with ANY fused
        weight therefore always swaps **in place** (its reads pause for
        the window; deep-net tenants sharing the stack keep serving),
        and the anchor tenant — whose reads may never pause — cannot
        swap at all while fused.
        """
        self._check_tenant(tenant)
        if not self._cache:
            raise RuntimeError("nothing programmed; call program_params "
                               "before begin_swap")
        if self._swap is not None:
            raise RuntimeError("a hot-swap is already in flight; promote() "
                               "or abort_swap() first")
        resident = tenant in self._programmed_leaves
        fused = resident and any(
            bank.is_fused(tenant) for bank in self._cache.values()
            if bank.has_tenant(tenant))
        if fused and tenant == self.anchor:
            raise RuntimeError(
                f"tenant {tenant!r} holds expansion-fused planes (both "
                f"RE high — no write shadow) and anchors the stack, so "
                f"its reads cannot pause for an in-place rewrite; "
                f"expansion-mode anchor deploys are cold deploys "
                f"(evict/reprogram), or program the anchor in deep-net "
                f"layout to hot-swap it")
        n_free = min(bank.n_free for bank in self._cache.values())
        if fused:
            # overlap refused: rewrite the fused tenant's own pair with
            # reads paused, whatever free planes exist
            n_free = 0
        if n_free == 0 and not fused:
            others = sorted(t for t in self._programmed_leaves
                            if t != tenant)
            if not resident:
                raise RuntimeError(
                    f"cannot live-deploy tenant {tenant!r}: stack is full "
                    f"({self.stack_planes} planes hold tenant(s) "
                    f"{others}); evict_tenant() first")
            if tenant == self.anchor:
                raise RuntimeError(
                    f"tenant {tenant!r} has no free write plane: the "
                    f"{self.stack_planes}-plane stack also holds "
                    f"tenant(s) {others}, and the anchor tenant cannot "
                    f"pause for an in-place rewrite; swap or evict one "
                    f"of {others} first")
        in_place = resident and n_free == 0
        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        if any(isinstance(w, jax.core.Tracer) for _, w in leaves):
            raise TypeError("begin_swap needs concrete arrays (eager, "
                            "outside jit)")
        programs = []
        for name, w, n_in in self._eligible(leaves):
            if name not in self._cache:
                raise ValueError(
                    f"swap tree carries {name!r} which has no resident "
                    f"tiles; hot-swap reprograms existing planes only")
            pw = self._cache[name].any_plane
            k = math.prod(w.shape[:n_in])
            w2d = jnp.asarray(w, jnp.float32).reshape(k, -1)
            if (k, w2d.shape[1]) != (pw.k, pw.n):
                raise ValueError(
                    f"{name}: swap shape {(k, w2d.shape[1])} != resident "
                    f"{(pw.k, pw.n)}")
            programs.append(ChunkedProgram(name, w2d, self.cfg))
        missing = set(self._cache) - {cp.name for cp in programs}
        if missing:
            raise ValueError(
                f"swap tree is missing resident tiles: {sorted(missing)}")
        if not in_place:
            # reserve the write target up front (all validation passed):
            # the staging role keeps a concurrent new-tenant deploy from
            # claiming the very plane this swap lands on at promote()
            for bank in self._cache.values():
                bank.reserve_staging()
        self._swap = SwapPlan(programs, tuple(w for _, w in leaves), params,
                              tenant=tenant, in_place=in_place)
        self._swap_t0 = time.perf_counter()
        return self._swap

    def write_chunks(self, n: int = 1) -> int:
        """Program up to ``n`` write-latency-costed chunks of the staged
        swap (each is one t_write pulse in the device-time model); returns
        the number of chunks still unwritten."""
        if self._swap is None:
            raise RuntimeError("no hot-swap in flight")
        for _ in range(n):
            if self._swap.done:
                break
            finished = self._swap.write_chunk()
            self._event("swap_chunks", "crossstack_swap_chunks_total",
                        "write-latency-costed chunks programmed into "
                        "swap targets", tenant=self._swap.tenant)
            if finished is not None:
                staged = finished.finish()
                # write-verify against an independent one-shot programming
                # (paced here, inside the overlap window — not at the flip)
                finished.verify(staged)
                self._swap.staged[finished.name] = (staged, finished.fp)
        return self._swap.remaining

    def promote(self) -> Any:
        """Atomically land the freshly written plane set.

        Every staged plane was already write-verified against an
        independent one-shot programming when its last chunk landed
        (``ChunkedProgram.verify``); this gate checks completeness and
        ownership — every tile must have been staged by THIS plan, not a
        stale or foreign one — before any bank changes, so a read can
        never observe a mixed-plane state.  A staged plan lands every
        bank's staging slot and retargets the tenant's read-enable (its
        previous slot reverts to free); an in-place plan rewrites the
        tenant's own slot (and un-pauses its reads).  Returns the
        promoted params tree (the caller serves embeddings/norms from
        it).
        """
        plan = self._swap
        if plan is None:
            raise RuntimeError("no hot-swap in flight")
        if not plan.done:
            raise RuntimeError(
                f"swap not complete: {plan.remaining} chunks unwritten")
        for name, fp in plan.expected_fingerprints.items():
            got = plan.staged.get(name)
            if got is None or got[1] != fp:
                raise RuntimeError(
                    f"{name}: staged plane fingerprint "
                    f"{got[1] if got else None} != checkpoint {fp}; "
                    f"refusing to promote")
        for cp in plan.programs:
            bank = self._cache[cp.name]
            pw, fp = plan.staged[cp.name]
            if plan.in_place:
                bank.assign(plan.tenant, pw, fp)
            else:
                bank.land_staged(plan.tenant, pw, fp)
        self._programmed_leaves[plan.tenant] = plan.leaves
        self._versions[plan.tenant] = self._versions.get(plan.tenant, 0) + 1
        lifecycle = "in_place" if plan.in_place else "staged"
        self._event("swaps", "crossstack_swaps_total",
                    "promoted plane-set swaps, by lifecycle",
                    tenant=plan.tenant, lifecycle=lifecycle)
        if self._swap_t0 is not None:
            obs.tracer().record(
                "executor_swap", self._swap_t0, time.perf_counter(),
                tenant=plan.tenant, lifecycle=lifecycle,
                chunks=plan.total_chunks,
                device_write_s=plan.device_write_time())
        self._swap = None
        self._swap_t0 = None
        return plan.params

    def abort_swap(self) -> None:
        """Drop an in-flight swap; every tenant's resident planes keep
        serving (written-and-verified planes are buffered in the plan and
        never touch a bank before promote, so abort is pure discard —
        a staged plan's reserved slots simply revert to free)."""
        if self._swap is not None:
            obs.registry().counter(
                "crossstack_swap_aborts_total",
                help="in-flight swaps discarded before promote").inc(
                    tenant=self._swap.tenant)
            if not self._swap.in_place:
                for bank in self._cache.values():
                    bank.release_staging()
        self._swap = None
        self._swap_t0 = None

    def swap(self, params: Any, chunk_burst: int = 64,
             tenant: str = "A") -> Dict[str, Any]:
        """Blocking convenience swap: stage, write every chunk, promote.

        The overlapped serving path (serve/hotswap.py) interleaves
        ``write_chunks`` with decode steps instead; this is the
        stop-the-world comparison point and the API for offline reloads.
        """
        plan = self.begin_swap(params, tenant=tenant)
        while not plan.done:
            self.write_chunks(chunk_burst)
        self.promote()
        return {"n_tiles": len(plan.programs),
                "n_chunks": plan.total_chunks,
                "tenant": tenant,
                "swap_mode": "in_place" if plan.in_place else "staged",
                "device_write_s": plan.device_write_time(),
                "programmed_version": self.version(tenant)}

    def evict_tenant(self, tenant: str) -> None:
        """Evict a resident tenant; its slot in every bank reverts to
        free (the anchor tenant cannot be evicted — reprogram it via
        swap instead).

        Refused outright while ANY :class:`SwapPlan` is in flight: every
        plan targets the same weight set the banks hold, and changing
        the residency registry mid-swap is exactly the race the old
        ``clear_twin`` API allowed (it silently discarded an in-flight
        staged shadow).  ``promote()`` or ``abort_swap()`` first.
        """
        self._check_tenant(tenant)
        if tenant == self.anchor:
            raise ValueError(
                f"tenant {tenant!r} anchors the plane banks; "
                f"swap(params) to replace its weights")
        if self._swap is not None:
            raise RuntimeError(
                f"cannot evict tenant {tenant!r}: a swap plan is in "
                f"flight over this stack's weights; promote() or "
                f"abort_swap() first")
        if tenant not in self._programmed_leaves:
            return
        for bank in self._cache.values():
            if bank.has_tenant(tenant):
                bank.evict(tenant)
        del self._programmed_leaves[tenant]

    # -- bookkeeping ---------------------------------------------------------

    @property
    def n_resident(self) -> int:
        return len(self._cache)

    @property
    def n_devices(self) -> int:
        """Programmed memristors serving reads (read-active planes) —
        the same quantity reported before plane banking, so bench
        trajectories stay comparable."""
        return sum(bank.n_devices for bank in self._cache.values())

    @property
    def n_devices_physical(self) -> int:
        """Total memristors in the stacks, all plane slots included."""
        return sum(bank.n_devices_physical
                   for bank in self._cache.values())

    @contextlib.contextmanager
    def activate(self):
        global _ACTIVE
        prev, _ACTIVE = _ACTIVE, self
        try:
            yield self
        finally:
            _ACTIVE = prev


# -- routing: active executor + name scopes (trace-time Python state) -------

_ACTIVE: Optional[CrossbarExecutor] = None
_SCOPE: List[str] = []


def active() -> Optional[CrossbarExecutor]:
    return _ACTIVE


@contextlib.contextmanager
def scope(name: Any):
    """Push a name-scope segment (layer index, module name) for routing."""
    _SCOPE.append(str(name))
    try:
        yield
    finally:
        _SCOPE.pop()


def scoped(name: str) -> str:
    return ".".join(_SCOPE + [name]) if _SCOPE else name


def crossbar_linear(x: jax.Array, w: jax.Array, name: str,
                    digital=None) -> jax.Array:
    """Drop-in linear: resident-crossbar read when an executor is active
    and holds the scoped weight, else the caller's digital formulation.

    ``digital`` is a thunk so the digital path keeps its exact dtype /
    sharding-constraint behavior (bf16 einsums, TP matmul variants) with
    zero cost on the crossbar path.
    """
    ex = _ACTIVE
    if ex is not None:
        full = scoped(name)
        if ex.has(full):
            return ex.linear(x, w, full)
    if digital is None:
        # no axes-guessing fallback: only the executor knows how many input
        # axes a named weight contracts (attention wo contracts two)
        raise ValueError(
            f"no resident tiles for {scoped(name)!r} and no digital "
            f"fallback provided")
    return digital()
