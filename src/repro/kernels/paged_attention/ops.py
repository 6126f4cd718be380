"""Shape-checked entry point + two-lane dispatch for paged attention.

Mirrors crossbar_mac's layering: ops validates/normalizes operands and
dispatches a kernel; the kernels stay pure shape-in/shape-out Pallas
calls.  No padding is needed here — the serving tier guarantees
``page_size | max_len`` (kv_pool.py enforces it), so the gathered depth
is already the dense path's ``max_len``.

Two lanes, dispatched by window size (``lane="auto"``):

* **scratch** (``paged_attention_kernel``) — gather-then-SDPA, bitwise
  vs ref/dense; peak VMEM linear in the window.  The small-window fast
  path and the oracle.
* **streamed** (``paged_attention_streamed``) — block-streamed online
  softmax, double-buffered page-block prefetch, O(block_pages) VMEM;
  bounded-ulp + argmax-stable vs the scratch lane.  Selected when the
  table is at least ``stream_min_pages`` pages wide (0 disables it).

Every dispatch lands in the global telemetry registry as
``crossstack_dispatch_total{path=paged_scratch|paged_streamed,
geometry}`` — bumped per call, i.e. per trace under jit, the same
accounting ``core/engine.matmul`` uses — so CI can pin which lane
served each decode closure (``paged_path_calls`` is the summed view).
**No fallback**: a lane that fails to trace, lower or compile raises
to the caller; nothing reroutes to another lane or to a reference.

Page tables may ALIAS: no validation here (or in the kernels) assumes
table entries are unique across rows.  Refcounted prefix sharing
(serve/kv_pool.py) points several rows' tables at the same physical
pages, and the read-only gather makes that indistinguishable from
private copies — see docs/KERNELS.md, "Aliased page tables are
in-contract".
"""
from __future__ import annotations

from collections.abc import Mapping

import jax.numpy as jnp

from repro import obs
from repro.kernels.paged_attention import kernel as _kernel_mod
from repro.kernels.paged_attention.kernel import paged_attention_kernel

_DISPATCH = "crossstack_dispatch_total"


def _count_dispatch(path: str, p_seq: int, ps: int) -> None:
    obs.registry().counter(
        _DISPATCH,
        help="engine.matmul dispatches per execution path, bumped per "
             "call (= per trace under jit), labeled by KxN geometry",
    ).inc(path=path, geometry=f"{p_seq}x{ps}")


class _PagedPathCallsView(Mapping):
    """Read-only view over the paged-attention dispatch counters, summed
    across geometries (``paged_path_calls["paged_streamed"]``); the
    registry keeps the per-geometry split."""

    _PATHS = ("paged_scratch", "paged_streamed")

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
        return f"paged_path_calls({dict(self)})"


paged_path_calls = _PagedPathCallsView()


def paged_attention(q, k_pages, v_pages, page_table, kv_len, q_offset,
                    *, causal: bool = True, lane: str = "auto",
                    stream_min_pages: int = 0, block_pages: int = 16):
    """Ragged paged decode attention; see kernel.py for the per-lane
    contracts.

    ``lane``: ``"auto"`` (streamed iff ``stream_min_pages > 0`` and the
    table is at least that many pages wide), ``"scratch"``, or
    ``"streamed"``.  ``block_pages`` sizes the streamed lane's page
    blocks (clamped to a divisor of the table width).
    """
    b, sq, hq, hd = q.shape
    if k_pages.shape != v_pages.shape:
        raise ValueError(f"k/v page pools disagree: {k_pages.shape} vs "
                         f"{v_pages.shape}")
    p1, ps, kv, hd2 = k_pages.shape
    if hd2 != hd:
        raise ValueError(f"head_dim mismatch: q {hd} vs pages {hd2}")
    if hq % kv:
        raise ValueError(f"n_heads {hq} not a multiple of kv heads {kv}")
    if page_table.shape[0] != b:
        raise ValueError(f"page_table rows {page_table.shape[0]} != "
                         f"batch {b}")
    kv_len = jnp.asarray(kv_len)
    q_offset = jnp.asarray(q_offset)
    if kv_len.shape != (b,) or q_offset.shape != (b,):
        raise ValueError(f"kv_len/q_offset want shape ({b},), got "
                         f"{kv_len.shape}/{q_offset.shape}")
    if lane not in ("auto", "scratch", "streamed"):
        raise ValueError(f"unknown lane {lane!r} (want auto | scratch | "
                         f"streamed)")
    p_seq = page_table.shape[1]
    if lane == "auto":
        lane = ("streamed" if stream_min_pages > 0
                and p_seq >= stream_min_pages else "scratch")
    page_table = page_table.astype(jnp.int32)
    if lane == "streamed":
        out = _kernel_mod.paged_attention_streamed(
            q, k_pages, v_pages, page_table, kv_len, q_offset,
            causal=causal, block_pages=block_pages)
        _count_dispatch("paged_streamed", p_seq, ps)
        return out
    _count_dispatch("paged_scratch", p_seq, ps)
    return paged_attention_kernel(q, k_pages, v_pages, page_table, kv_len,
                                  q_offset, causal=causal)
