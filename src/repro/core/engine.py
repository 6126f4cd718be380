"""CrossStackEngine — tiles arbitrary matmuls onto stacked crossbar pairs.

This is the bridge between the device-level digital twin and the model zoo:
any linear layer ``y = x @ W`` can be *programmed* onto a grid of CrossStack
tiles and executed with bit-exact crossbar arithmetic:

  * K (input/row) dimension   -> tiles of ``tile_rows`` rows per plane.
      - expansion mode: adjacent row-tiles are stacked onto the two planes
        and their currents sum in ANALOG on the shared column before the
        ADC (one conversion per 2*tile_rows rows — the paper's doubled-n).
      - deep-net mode: one plane is read per beat (ADC per tile_rows rows);
        the other plane is concurrently programmed (see pipeline.py).
  * N (output/col) dimension  -> tiles of ``tile_cols`` columns.
  * weights -> differential single-bit (or multi-bit) cell planes (quant.py).
  * inputs  -> two's-complement bit-serial pulse trains.
  * each (tile, slice, pulse) read passes through a saturating ADC before
    the digital shift-add recombine — quantization error is faithful.

Two execution paths share one ``ProgrammedLinear`` representation:
  * digital twin (integer-exact; also what kernels/crossbar_mac computes),
  * analog (conductance domain: device variability, access-transistor R,
    first-order IR attenuation) for fidelity studies on small layers.
"""
from __future__ import annotations

import dataclasses
import functools
from collections.abc import Mapping
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import quant
from repro.core.device import DeviceConfig
from repro.core.quant import QuantConfig
from repro.core.timing import PAPER, CrossStackParams
from repro.core import ir_drop


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    tile_rows: int = 128
    tile_cols: int = 128
    quant: QuantConfig = QuantConfig()
    mode: str = "expansion"            # "expansion" | "deepnet"
    params: CrossStackParams = PAPER
    use_kernel: bool = False           # route MAC through the Pallas kernel
    swap_leakage: bool = False         # perturb reads with write-plane
    # leakage while a hot-swap is in flight (fidelity studies; breaks
    # bit-exactness of mid-swap reads by at most the ADC residual)
    device: DeviceConfig = DeviceConfig()  # vertical stack geometry

    @property
    def rows_per_adc(self) -> int:
        """Rows summed in analog before one ADC conversion."""
        return 2 * self.tile_rows if self.mode == "expansion" else self.tile_rows

    @property
    def stack_planes(self) -> int:
        """Planes stacked per cell site (the bank height N)."""
        return self.device.stack_planes


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ProgrammedLinear:
    """Crossbar-resident weights: differential cell-code planes + scales."""
    pos: jax.Array      # (S, T, R, N_pad) int8 cell codes, T row-tiles
    neg: jax.Array      # (S, T, R, N_pad) int8
    w_scale: jax.Array  # (1, N_pad) or scalar
    k: int              # logical input dim
    n: int              # logical output dim

    def tree_flatten(self):
        return (self.pos, self.neg, self.w_scale), (self.k, self.n)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def n_devices(self) -> int:
        return 2 * int(jnp.size(self.pos))  # pos + neg planes


def _pad_to(x: jax.Array, size: int, axis: int) -> jax.Array:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def program(w: jax.Array, cfg: EngineConfig) -> ProgrammedLinear:
    """Quantize and map a float (K, N) weight matrix onto crossbar tiles."""
    k, n = w.shape
    q = cfg.quant
    w_int, w_scale = quant.quantize_weights(w, q)
    r = cfg.tile_rows
    t = -(-k // r)
    n_pad = -(-n // cfg.tile_cols) * cfg.tile_cols
    if q.per_channel:
        w_scale = _pad_to(w_scale, n_pad, axis=1)
    pos, neg = _cell_planes(w_int, q, t, r, n_pad)
    return ProgrammedLinear(pos, neg, w_scale, k, n)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _cell_planes(w_int: jax.Array, q: QuantConfig, t: int, r: int,
                 n_pad: int):
    """Signed integer weights (K, N) -> int8 (S, T, R, N_pad) pos/neg
    cell-code planes, zero-padded.

    One fused program: the digit extraction is exact integer arithmetic,
    so jitting it leaves every code as the eager ops would produce it,
    while XLA writes the int8 planes directly — the (S, K, N) int32
    digit stacks never materialize, and programming an LM head peaks
    near its int8 output.  (Quantization stays outside: under jit XLA
    may rewrite ``amax / qmax`` into a reciprocal multiply, which moves
    scales by an ulp and can flip a code at a rounding boundary.)"""
    w_int = _pad_to(_pad_to(w_int, t * r, axis=0), n_pad, axis=1)
    pos, neg = quant.to_slices(w_int, q)               # (S, T*R, N_pad)
    s = q.n_slices
    return (pos.reshape(s, t, r, n_pad).astype(jnp.int8),
            neg.reshape(s, t, r, n_pad).astype(jnp.int8))


# ---------------------------------------------------------------------------
# Digital-twin execution (integer-exact; oracle for kernels/crossbar_mac)
# ---------------------------------------------------------------------------

def _adc_codes(acc: jax.Array, cfg: EngineConfig) -> jax.Array:
    """Saturating ADC in code units.

    acc holds per-column analog sums in [0, rows_per_adc * (base-1)].
    The ADC maps this to 2**adc_bits uniform levels with clamp; we return
    the dequantized value on the same scale so recombination is a pure
    shift-add.
    """
    q = cfg.quant
    base = 2 ** q.bits_per_cell
    full_scale = cfg.rows_per_adc * (base - 1)
    levels = 2.0 ** q.adc_bits - 1.0
    lsb = full_scale / levels
    code = jnp.clip(jnp.round(acc / lsb), 0.0, levels)
    return code * lsb


# host-side dispatch accounting (bumped per call, i.e. per trace under
# jit): every matmul dispatch lands in the global telemetry registry as
# crossstack_dispatch_total{path, geometry}.  Benches and the overlap
# property test snapshot these around a decode closure's trace to prove
# the hot path lowered the Pallas kernel and not the reference scan.
_DISPATCH = "crossstack_dispatch_total"


def _count_dispatch(path: str, pw: "ProgrammedLinear") -> None:
    obs.registry().counter(
        _DISPATCH,
        help="engine.matmul dispatches per execution path, bumped per "
             "call (= per trace under jit), labeled by KxN geometry",
    ).inc(path=path, geometry=f"{pw.k}x{pw.n}")


class _PathCallsView(Mapping):
    """Deprecated read-only alias for the registry's dispatch counters.

    Kept so pre-registry callers (``eng.path_calls["kernel"]``,
    ``dict(eng.path_calls)``, equality against plain dicts) keep
    working; new code should query
    ``obs.registry().total("crossstack_dispatch_total", path=...)``,
    which also exposes the per-geometry split this view sums away.
    """

    _PATHS = ("kernel", "reference")

    def __getitem__(self, key: str) -> int:
        if key not in self._PATHS:
            raise KeyError(key)
        return int(obs.registry().total(_DISPATCH, path=key))

    def __iter__(self):
        return iter(self._PATHS)

    def __len__(self) -> int:
        return len(self._PATHS)

    def __eq__(self, other) -> bool:
        if isinstance(other, (Mapping, dict)):
            return dict(self) == dict(other)
        return NotImplemented

    __hash__ = None

    def __repr__(self) -> str:
        return f"path_calls({dict(self)})"


path_calls = _PathCallsView()


def matmul(x: jax.Array, pw: ProgrammedLinear, cfg: EngineConfig,
           leak_codes=0.0) -> jax.Array:
    """Bit-exact crossbar execution of ``x @ W`` for x of shape (..., K).

    ``leak_codes`` is the common-mode write-plane leakage in pre-ADC code
    units (deep-net overlap; see ``planes.write_leak_codes``) — a python
    float or a *traced* scalar.  The Pallas kernel fuses it into its ADC
    stage, so ``use_kernel`` traffic stays on the kernel path during an
    overlap read (precisely when throughput matters most); as a traced
    operand it changes value between decode steps without re-lowering.
    """
    if cfg.use_kernel:
        from repro.kernels.crossbar_mac import ops as cb_ops
        _count_dispatch("kernel", pw)
        return cb_ops.crossbar_matmul(x, pw, cfg, leak_codes=leak_codes)
    return matmul_reference(x, pw, cfg, leak_codes=leak_codes)


def matmul_reference(x: jax.Array, pw: ProgrammedLinear, cfg: EngineConfig,
                     leak_codes=0.0) -> jax.Array:
    """Scan-based reference: one (pulse, slice) step at a time, ADC fused.

    The einsum formulation (kept as ``_matmul_reference_einsum``) holds the
    full pre-ADC accumulator of shape (in_bits, S, B, T, N) live at once;
    scanning over the in_bits * S (pulse, slice) pairs and applying the ADC
    inside each step bounds peak activation memory at O(B * T * N) — the
    hardware reads one pulse against one cell plane per beat anyway, so the
    scan is also the faithful schedule.

    ``leak_codes`` adds the write-plane subthreshold leakage of an
    in-flight deep-net shadow write to BOTH differential columns before
    each ADC conversion (modes.deepnet_read at executor scale): the term
    is common-mode and survives only through ADC quantization.
    """
    _count_dispatch("reference", pw)
    q = cfg.quant
    lead = x.shape[:-1]
    xb = x.reshape(-1, x.shape[-1])                     # (B, K)
    x_int, x_scale = quant.quantize_inputs(xb, q)
    s, t, r, n_pad = pw.pos.shape
    x_int = _pad_to(x_int, t * r, axis=-1).reshape(-1, t, r)
    bits = quant.to_bit_serial(x_int, q)                # (b, B, T, R)
    bitw = quant.bit_weights(q)                         # (b,)
    slcw = quant.slice_weights(q)                       # (S,)

    pos = pw.pos.astype(jnp.float32)
    neg = pw.neg.astype(jnp.float32)
    b_in = bits.shape[0]
    bsz = x_int.shape[0]

    def step(y_acc, idx):
        a, sl = idx // s, idx % s
        xa = jax.lax.dynamic_index_in_dim(bits, a, 0, keepdims=False)
        p_s = jax.lax.dynamic_index_in_dim(pos, sl, 0, keepdims=False)
        n_s = jax.lax.dynamic_index_in_dim(neg, sl, 0, keepdims=False)
        # analog column sums of ONE pulse against ONE cell plane: (B, T, N)
        acc_p = jnp.einsum("btr,trn->btn", xa, p_s)
        acc_n = jnp.einsum("btr,trn->btn", xa, n_s)
        if cfg.mode == "expansion" and t % 2 == 0 and t >= 2:
            # adjacent row-tiles stacked on the two planes: analog sum first
            acc_p = acc_p.reshape(bsz, t // 2, 2, n_pad).sum(axis=2)
            acc_n = acc_n.reshape(bsz, t // 2, 2, n_pad).sum(axis=2)
        d = (_adc_codes(acc_p + leak_codes, cfg)
             - _adc_codes(acc_n + leak_codes, cfg))
        return y_acc + bitw[a] * slcw[sl] * d.sum(axis=1), None

    y_int, _ = jax.lax.scan(step, jnp.zeros((bsz, n_pad), jnp.float32),
                            jnp.arange(b_in * s))
    y = y_int * x_scale * pw.w_scale[..., :n_pad]
    return y[:, : pw.n].reshape(*lead, pw.n)


def _matmul_reference_einsum(x: jax.Array, pw: ProgrammedLinear,
                             cfg: EngineConfig) -> jax.Array:
    """Original all-at-once einsum formulation.

    O(in_bits * S * B * T * N) peak memory; retained as the oracle the
    scan-based reference must match bit for bit (tests/test_executor.py).
    """
    q = cfg.quant
    lead = x.shape[:-1]
    xb = x.reshape(-1, x.shape[-1])                     # (B, K)
    x_int, x_scale = quant.quantize_inputs(xb, q)
    s, t, r, n_pad = pw.pos.shape
    x_int = _pad_to(x_int, t * r, axis=-1).reshape(-1, t, r)
    bits = quant.to_bit_serial(x_int, q)                # (b, B, T, R)
    bitw = quant.bit_weights(q)                         # (b,)
    slcw = quant.slice_weights(q)                       # (S,)

    pos = pw.pos.astype(jnp.float32)
    neg = pw.neg.astype(jnp.float32)

    # per (pulse b, slice s, row-tile t): analog column sums
    acc_p = jnp.einsum("abtr,strn->asbtn", bits, pos)
    acc_n = jnp.einsum("abtr,strn->asbtn", bits, neg)

    if cfg.mode == "expansion" and t % 2 == 0 and t >= 2:
        # adjacent row-tiles stacked on the two planes: analog sum first
        acc_p = acc_p.reshape(*acc_p.shape[:3], t // 2, 2, n_pad).sum(axis=4)
        acc_n = acc_n.reshape(*acc_n.shape[:3], t // 2, 2, n_pad).sum(axis=4)

    acc_p = _adc_codes(acc_p, cfg)
    acc_n = _adc_codes(acc_n, cfg)

    y_int = jnp.einsum("asbtn,a,s->bn", acc_p - acc_n, bitw, slcw)
    y = y_int * x_scale * pw.w_scale[..., :n_pad]
    return y[:, : pw.n].reshape(*lead, pw.n)


def linear(x: jax.Array, w: jax.Array, cfg: EngineConfig) -> jax.Array:
    """Program-and-run convenience op (QAT / fidelity studies).

    Differentiable end to end via the STE quantizers.
    """
    return matmul(x, program(w, cfg), cfg)


# ---------------------------------------------------------------------------
# Analog execution (conductance domain, non-idealities)
# ---------------------------------------------------------------------------

def matmul_analog(key: Optional[jax.Array], x: jax.Array,
                  pw: ProgrammedLinear, cfg: EngineConfig,
                  noise: bool = True, ir_comp: bool = False) -> jax.Array:
    """Conductance-domain execution with Table-I non-idealities.

    Each (slice, row-tile) is a physical plane pair; cell codes map to
    conductances in [g_reset, g_set]; inputs map to read voltages; column
    currents pass a current-domain ADC.  Meant for small fidelity studies
    (the digital twin is the production path).
    """
    p = cfg.params
    q = cfg.quant
    lead = x.shape[:-1]
    xb = x.reshape(-1, x.shape[-1])
    x_int, x_scale = quant.quantize_inputs(xb, q)
    s, t, r, n_pad = pw.pos.shape
    x_int = _pad_to(x_int, t * r, axis=-1).reshape(-1, t, r)
    bits = quant.to_bit_serial(x_int, q)                 # (b, B, T, R)
    v_pulses = bits * p.v_read                           # 0 / V_read drives

    g_pos = quant.slices_to_conductance(pw.pos, q, p.g_reset, p.g_set)
    g_neg = quant.slices_to_conductance(pw.neg, q, p.g_reset, p.g_set)
    if noise:
        if key is None:
            raise ValueError("matmul_analog(noise=True) needs a PRNG key")
        kp, kn = jax.random.split(key)
        frac_p = (g_pos - p.g_reset) / (p.g_set - p.g_reset)
        frac_n = (g_neg - p.g_reset) / (p.g_set - p.g_reset)
        sig_p = p.r_reset_tol + (p.r_set_tol - p.r_reset_tol) * frac_p
        sig_n = p.r_reset_tol + (p.r_set_tol - p.r_reset_tol) * frac_n
        g_pos = g_pos * (1.0 + sig_p * jax.random.normal(kp, g_pos.shape))
        g_neg = g_neg * (1.0 + sig_n * jax.random.normal(kn, g_neg.shape))

    # access transistor in series
    g_pos = 1.0 / (1.0 / g_pos + p.r_on_transistor)
    g_neg = 1.0 / (1.0 / g_neg + p.r_on_transistor)

    i_p = jnp.einsum("abtr,strn->asbtn", v_pulses, g_pos)
    i_n = jnp.einsum("abtr,strn->asbtn", v_pulses, g_neg)

    if ir_comp:
        # first-order column attenuation for a nominal all-SET tile
        g_nom = jnp.full((r, n_pad), p.g_set)
        atten = ir_drop.attenuation_map(
            g_nom, jnp.full((r,), p.v_read), p.r_wire)
        i_p = i_p * atten
        i_n = i_n * atten

    if cfg.mode == "expansion" and t % 2 == 0 and t >= 2:
        i_p = i_p.reshape(*i_p.shape[:3], t // 2, 2, n_pad).sum(axis=4)
        i_n = i_n.reshape(*i_n.shape[:3], t // 2, 2, n_pad).sum(axis=4)

    # current-domain ADC: full scale = every summed cell at G_set, V_read
    g_fs = 1.0 / (p.r_set + p.r_on_transistor)
    i_fs = cfg.rows_per_adc * p.v_read * g_fs
    levels = 2.0 ** q.adc_bits - 1.0
    lsb = i_fs / levels
    i_p = jnp.clip(jnp.round(i_p / lsb), 0.0, levels)
    i_n = jnp.clip(jnp.round(i_n / lsb), 0.0, levels)

    # Convert ADC codes back to cell-code units for the shift-add.  The
    # differential subtraction cancels the common g_reset pedestal (both
    # column groups have the same cell count), so one cell-code step
    # corresponds to the *spacing* conductance, not the absolute one.
    base = 2 ** q.bits_per_cell
    g_step = (1.0 / (1.0 / p.g_set + p.r_on_transistor)
              - 1.0 / (1.0 / p.g_reset + p.r_on_transistor)) / (base - 1)
    adc_codes_per_cell_code = (p.v_read * g_step) / lsb
    y_codes = (i_p - i_n) / adc_codes_per_cell_code
    bitw = quant.bit_weights(q)
    slcw = quant.slice_weights(q)
    y_int = jnp.einsum("asbtn,a,s->bn", y_codes, bitw, slcw)
    y = y_int * x_scale * pw.w_scale[..., :n_pad]
    return y[:, : pw.n].reshape(*lead, pw.n)
