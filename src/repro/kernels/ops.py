"""Public jit'd wrappers for the Pallas kernels.

Each op pads inputs to hardware-aligned tiles (lane = 128, MXU-friendly
contraction dims), dispatches to the Pallas kernel, and slices the result
back.  On CPU hosts the kernels execute in interpret mode (the kernel body
runs as traced jnp ops) — the TPU path is identical code with
interpret=False.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels import bucket_topk as _bt
from repro.kernels import fused_query as _fq
from repro.kernels import hamming as _hm
from repro.kernels import simhash as _sh

LANE = 128


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, axis: int, multiple: int, value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def simhash(
    x: jax.Array,            # [n, d] float
    hyperplanes: jax.Array,  # [L, k, d] float
    *,
    tn: int = 256,
    td: int = 512,
    packed: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """LSH sketch codes: uint32 [n, L] per-table codes, or with
    packed=True dense `core.packed` words uint32 [n, ceil(L*k/32)]
    emitted directly in-kernel.  Matches `ref.simhash_ref` (resp. its
    pack_codes composition)."""
    interpret = _on_cpu() if interpret is None else interpret
    n, d = x.shape
    L, k, _ = hyperplanes.shape
    h_t = hyperplanes.reshape(L * k, d).T.astype(jnp.float32)  # [d, L*k]
    h_t = _pad_to(h_t, 1, LANE)
    tn_eff = min(tn, max(8, n))
    x_p = _pad_to(x.astype(jnp.float32), 0, tn_eff)
    td_eff = min(td, d) if d % min(td, d) == 0 else d
    # choose a td that divides d (fall back to whole-d single step)
    if d % td == 0:
        td_eff = td
    else:
        td_eff = d
        h_t = h_t  # single d-step
    x_p = _pad_to(x_p, 1, td_eff)
    h_t = _pad_to(h_t, 0, td_eff)
    out = _sh.simhash_pallas(
        x_p, h_t, k=k, L=L, tn=tn_eff, td=td_eff, packed=packed,
        interpret=interpret,
    )
    return out[:n]


def bucket_topk(
    q: jax.Array,      # [b, d] float
    cand: jax.Array,   # [b, kc, d] float candidate payloads
    valid: jax.Array,  # bool [b, kc]
    m: int,
    *,
    tb: int = 8,
    interpret: bool | None = None,
):
    """Fused score + top-m. Returns (scores [b, m] f32, idx [b, m] i32).
    Matches `ref.bucket_topk_ref` (ties -> lowest index).  The kernel
    walks kc in 128-lane chunks with a running top m, so its VMEM use
    does not grow with the candidate count."""
    interpret = _on_cpu() if interpret is None else interpret
    b, kc, d = cand.shape
    tb_eff = min(tb, max(1, b))
    q_p = _pad_to(q.astype(jnp.float32), 0, tb_eff)
    cand_p = _pad_to(cand.astype(jnp.float32), 0, tb_eff)
    valid_p = _pad_to(valid.astype(jnp.int32), 0, tb_eff)
    q_p = _pad_to(q_p, 1, LANE)
    cand_p = _pad_to(_pad_to(cand_p, 2, LANE), 1, _bt.TKC)
    valid_p = _pad_to(valid_p, 1, _bt.TKC)
    s, i = _bt.bucket_topk_pallas(
        q_p, cand_p, valid_p, m=m, tb=tb_eff, interpret=interpret
    )
    return s[:b], i[:b]


def hamming(
    codes: jax.Array,       # [n] uint32 or [n, W] packed words
    cand_codes: jax.Array,  # [n, kc] uint32 or [n, kc, W] packed words
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Hamming distances int32 [n, kc].

    Single-word inputs ([n] vs [n, kc]) match `ref.hamming_ref`;
    multi-word packed rows ([n, W] vs [n, kc, W], the `core.packed`
    layout) match `ref.hamming_words_ref` — this shape is the staged
    scoring primitive of `score="hamming"` runtimes.  Padded candidate
    columns return distance vs code 0 and are sliced off.  The row tiles
    keep each kernel's VMEM blocks far under the TPU's scoped limit at
    P*C = 1920 candidates per row."""
    interpret = _on_cpu() if interpret is None else interpret
    if cand_codes.ndim == 3:
        n, kc, w = cand_codes.shape
        tn_eff = min(16, max(8, n))
        codes_p = _pad_to(codes.astype(jnp.uint32), 0, tn_eff)
        # word-major for the kernel: unlike the fused path's view of the
        # store, this swaps a freshly gathered tensor, which may cost a
        # relayout per call
        cand_p = jnp.swapaxes(cand_codes.astype(jnp.uint32), 1, 2)
        cand_p = _pad_to(_pad_to(cand_p, 0, tn_eff),
                         2, LANE if not interpret else 8)
        out = _hm.hamming_words_pallas(
            codes_p, cand_p, tn=tn_eff, interpret=interpret
        )
        return out[:n, :kc]
    n, kc = cand_codes.shape
    tn_eff = min(256, max(8, n))
    codes_p = _pad_to(codes.astype(jnp.uint32), 0, tn_eff)
    cand_p = _pad_to(cand_codes.astype(jnp.uint32), 0, tn_eff)
    cand_p = _pad_to(cand_p, 1, LANE)
    out = _hm.hamming_pallas(codes_p, cand_p, tn=tn_eff, interpret=interpret)
    return out[:n, :kc]


def fused_query(
    ids_flat: jax.Array,   # int32 [T*NB, C] bucket slot ids (-1 = empty)
    pay_flat: jax.Array,   # [T*NB, C, D] f32 vectors or [T*NB, C, W] words
    q: jax.Array,          # [r, D] f32 queries or [r, W] packed query words
    fb: jax.Array,         # int32 [r, P] flattened bucket row per probe
    meta: jax.Array,       # int32 [r, 2] (probe-validity word, exclude id)
    *,
    m: int,
    score: str = "dot",
    tb: int | None = None,
    kc: int | None = None,
    tune_op: str = "fused_query",
    interpret: bool | None = None,
):
    """Fused gather -> score -> top-m (ids [r, m] i32, scores [r, m] f32).

    Matches `ref.fused_query_ref` — which routes through
    `core.scoring.dedupe_topk`, so fused results are bit-identical to
    the staged path by construction.  tb/kc default to the autotuned
    block shape for this device kind (`kernels.autotune`) under the
    `tune_op` key — the routed mesh stage sweeps separately as
    "fused_query_routed" since its row count is n·cap, not b·L; kc is
    the capacity pad multiple (bucket rows are padded to a whole number
    of candidate lanes)."""
    interpret = _on_cpu() if interpret is None else interpret
    tuned = autotune.get(tune_op)
    tb = int(tuned.get("tb", 8)) if tb is None else tb
    kc = int(tuned.get("kc", 8 if interpret else LANE)) if kc is None else kc
    r, _ = fb.shape
    c = ids_flat.shape[-1]
    kc_eff = min(kc, max(8, c)) if interpret else kc
    tb_eff = min(tb, max(1, r))
    with jax.named_scope("payload_prep"):
        ids_p = _pad_to(
            _pad_to(ids_flat.astype(jnp.int32), 1, kc_eff, value=-1),
            0, _fq.SLAB, value=-1)
        pay_p = _pad_to(pay_flat, 1, kc_eff)
        if score == "dot":
            pay_p = _pad_to(pay_p.astype(jnp.float32), 2,
                            8 if interpret else LANE)
            q_p = _pad_to(q.astype(jnp.float32), 1, 8 if interpret else LANE)
        else:
            # word-major view: the TPU keeps a [.., C, W] uint32 store with
            # C as its minor (lane) axis, so this swap moves no bytes there
            pay_p = jnp.swapaxes(pay_p.astype(jnp.uint32), 1, 2)
            q_p = q.astype(jnp.uint32)
        fb_p = jnp.clip(
            _pad_to(fb.astype(jnp.int32), 0, tb_eff), 0, ids_p.shape[0] - 1
        )
        meta_p = _pad_to(meta.astype(jnp.int32), 0, tb_eff)  # pad: pword 0
        q_p = _pad_to(q_p, 0, tb_eff)
    with jax.named_scope("fused_kernel"):
        ids_r, sc_r = _fq.fused_query_pallas(
            ids_p, pay_p, q_p, fb_p, meta_p,
            m=m, tb=tb_eff, score=score, interpret=interpret,
        )
    return ids_r[:r], sc_r[:r]


def fused_contains(
    ids_flat: jax.Array,   # int32 [T*NB, C]
    fb: jax.Array,         # int32 [r, P]
    meta: jax.Array,       # int32 [r, 2] (probe-validity word, target id)
    *,
    tb: int | None = None,
    tune_op: str = "fused_query",
    interpret: bool | None = None,
) -> jax.Array:
    """Fused membership probe: bool [r]. Matches `ref.fused_contains_ref`.
    Needs no payload, so it serves ids-only stores too."""
    interpret = _on_cpu() if interpret is None else interpret
    tuned = autotune.get(tune_op)
    tb = int(tuned.get("tb", 8)) if tb is None else tb
    r, _ = fb.shape
    c = ids_flat.shape[-1]
    kc_eff = min(8, max(1, c)) if interpret else LANE
    ids_p = _pad_to(_pad_to(ids_flat.astype(jnp.int32), 1, kc_eff, value=-1),
                    0, _fq.SLAB, value=-1)
    tb_eff = min(tb, max(1, r))
    fb_p = jnp.clip(
        _pad_to(fb.astype(jnp.int32), 0, tb_eff), 0, ids_p.shape[0] - 1
    )
    meta_p = _pad_to(meta.astype(jnp.int32), 0, tb_eff)
    hit = _fq.fused_contains_pallas(
        ids_p, fb_p, meta_p, tb=tb_eff, interpret=interpret
    )
    return hit[:r, 0] > 0
