"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

For every (arch x shape x mesh) JSON produced by launch/dryrun.py:
  compute_term    = HLO_FLOPs / (chips * 197e12)           [s]
  memory_term     = HLO_bytes / (chips * 819e9)            [s]
  collective_term = wire_bytes / (chips * 50e9)            [s]
with cost_analysis() reported per-device by XLA (chips divisor already
applied there => we use the per-device numbers directly), plus
  MODEL_FLOPS = 6 * N_active * D_tokens  (x3 for train: fwd+bwd)
and the useful-compute ratio MODEL_FLOPS / (HLO_FLOPs * chips).
"""

from __future__ import annotations

import glob
import json
import os

from repro.configs import get_config
from repro.configs.shapes import SHAPES

PEAK_FLOPS = 197e12     # bf16 / chip (v5e)
HBM_BW = 819e9          # B/s / chip
LINK_BW = 50e9          # B/s / link

Q_CHUNK = 1024          # must match models/layers.py
SSM_CHUNK = 256
MLSTM_CHUNK = 256


def _inner_scan_correction(arch: str, shape_name: str, chips: int) -> float:
    """Per-device FLOPs the HLO under-reports because the *inner* sequence
    scans (flash q-chunks, mamba/mLSTM chunks, sLSTM steps) stay as while
    loops even in the unrolled dry-run: XLA counts their bodies once, so we
    add (trips - 1) x body analytically.  Matmul terms are exact; the
    elementwise terms (softmax, gate math) are ~10% estimates.

    Train steps multiply by 4 (fwd body + remat recompute + ~2x bwd); all
    dims except possibly attention heads shard over the 256 chips.
    """
    cfg = get_config(arch)
    spec = SHAPES[shape_name]
    if spec.kind == "decode":
        return 0.0  # single-token step: inner scans have 1 trip
    B, S = spec.global_batch, spec.seq_len
    mult = 4.0 if spec.kind == "train" else 1.0
    total = 0.0

    n_attn = sum(1 for i in range(cfg.num_layers) if cfg.layer_kind(i) == "attn")
    n_mamba = sum(1 for i in range(cfg.num_layers) if cfg.layer_kind(i) == "mamba")
    n_mlstm = sum(1 for i in range(cfg.num_layers) if cfg.layer_kind(i) == "mlstm")
    n_slstm = sum(1 for i in range(cfg.num_layers) if cfg.layer_kind(i) == "slstm")

    # attention q-chunk scan (active when S > 2048)
    if n_attn and S > 2048:
        trips = S // Q_CHUNK
        # per chunk: scores + out einsums (2 x 2BCS*qdim) + softmax (~6BHCS)
        body = (4.0 * B * Q_CHUNK * S * cfg.q_dim
                + 6.0 * B * cfg.num_heads * Q_CHUNK * S)
        heads_sharded = cfg.num_kv_heads % 16 == 0
        body_dev = body / chips if heads_sharded else body / (chips / 16)
        total += n_attn * (trips - 1) * body_dev
        if cfg.encoder_layers and S > 2048:
            total += cfg.encoder_layers * (trips - 1) * body_dev

    # mamba chunked selective scan
    if n_mamba:
        q = SSM_CHUNK
        trips = S // q
        di, n = cfg.d_inner, cfg.mamba_d_state
        body = (2.0 * B * q * di * n            # y = h . C einsum
                + (4.0 * 8 + 5.0) * B * q * di * n)  # assoc scan + h_t
        total += n_mamba * (trips - 1) * body / chips
    # mLSTM chunkwise-parallel scan
    if n_mlstm:
        q = MLSTM_CHUNK
        trips = S // q
        hq, dh = cfg.num_heads, cfg.d_model // cfg.num_heads
        body = (6.0 * B * hq * q * q * dh       # scores/h_intra/n_intra
                + 4.0 * B * hq * q * dh * dh    # h_inter + C_new
                + 12.0 * B * hq * q * q)        # D/exp elementwise
        total += n_mlstm * (trips - 1) * body / chips
    # sLSTM time scan (inherently sequential)
    if n_slstm:
        hq, dh = cfg.num_heads, cfg.d_model // cfg.num_heads
        body = 2.0 * B * hq * dh * 4 * dh + 24.0 * B * hq * dh
        total += n_slstm * (S - 1) * body / chips
    total *= mult
    # loss-chunk scan (train only; stays a lax.scan even when unrolled so
    # the embedding-grad all-reduce is counted once, as in production):
    # logits matmul 2BSdV, x4 for fwd + remat recompute + ~2x bwd
    if spec.kind == "train":
        chunk = 512
        nc = -(-S // chunk)
        body = 4.0 * 2.0 * B * chunk * cfg.d_model * cfg.vocab_size
        total += (nc - 1) * body / chips
    return total


def model_flops(arch: str, shape_name: str) -> float:
    """6*N_active*D (dense/MoE) plus the inherent attention score/output
    FLOPs (which 6ND omits and which dominate >=32k prefill)."""
    cfg = get_config(arch)
    spec = SHAPES[shape_name]
    n_active = cfg.active_params_per_token()
    B, S = spec.global_batch, spec.seq_len

    def attn_flops_per_seq(s_ctx):
        """fwd score+output matmul FLOPs for one full sequence."""
        total = 0.0
        for i in range(cfg.num_layers):
            if cfg.layer_kind(i) != "attn":
                continue
            win = cfg.window_size if cfg.layer_is_local_attn(i) else 0
            # causal: sum_t min(t, win or t) ~ s*s/2 (or s*win)
            pairs = s_ctx * min(win, s_ctx) if win else s_ctx * s_ctx / 2.0
            total += 4.0 * pairs * cfg.q_dim
        for _ in range(cfg.encoder_layers):
            total += 4.0 * s_ctx * s_ctx * cfg.q_dim  # bidirectional
        return total

    if spec.kind == "train":
        tokens = B * S
        return 6.0 * n_active * tokens + 3.0 * B * attn_flops_per_seq(S)
    if spec.kind == "prefill":
        tokens = B * S
        return 2.0 * n_active * tokens + B * attn_flops_per_seq(S)
    # decode: one token per sequence; attention reads the S-deep cache
    dec_attn = 0.0
    for i in range(cfg.num_layers):
        if cfg.layer_kind(i) == "attn":
            win = cfg.window_size if cfg.layer_is_local_attn(i) else 0
            ctx = min(win, S) if win else S
            dec_attn += 4.0 * ctx * cfg.q_dim
    return 2.0 * n_active * B + B * dec_attn


def model_hbm_bytes(arch: str, shape_name: str, chips: int) -> float:
    """Analytic per-device HBM traffic lower-bound estimate (bytes/step).

    The HLO 'bytes accessed' from the CPU-backend compile wildly overstates
    TPU HBM traffic (CPU fusion is far weaker), so the memory roofline term
    uses this model: weights read once per pass (x3 passes for train with
    remat: fwd, recompute, bwd) + grad write + opt state rw + activation
    checkpoints rw + KV/state reads for decode.  Documented in
    EXPERIMENTS.md §Roofline.
    """
    cfg = get_config(arch)
    spec = SHAPES[shape_name]
    B, S = spec.global_batch, spec.seq_len
    p_total = cfg.total_params()
    w_bytes = 2.0 * p_total / chips               # bf16 shard
    d = cfg.d_model
    if spec.kind == "train":
        acts = 2.0 * B * S * d * (cfg.num_layers / max(cfg.scan_period, 1)) \
            * 2 / chips                           # period-boundary checkpoints rw
        opt = 2.0 * (4.0 if arch not in ("llama4-maverick-400b-a17b",
                                         "jamba-v0.1-52b") else 1.03) \
            * p_total / chips                     # m+v read+write
        return 3.0 * w_bytes + 2.0 * w_bytes + opt + acts  # 3 passes + grads
    if spec.kind == "prefill":
        acts = 2.0 * B * S * d * cfg.num_layers / chips
        return w_bytes + acts
    # decode: weights + full KV/recurrent state read
    kv = 0.0
    for i in range(cfg.num_layers):
        kind = cfg.layer_kind(i)
        if kind == "attn":
            win = cfg.window_size if cfg.layer_is_local_attn(i) else 0
            ctx = min(win, S) if win else S
            kv += 2.0 * B * ctx * cfg.kv_dim * 2
        elif kind == "mamba":
            kv += 4.0 * B * cfg.d_inner * cfg.mamba_d_state
        elif kind in ("mlstm", "slstm"):
            dh = d // cfg.num_heads
            kv += 4.0 * B * cfg.num_heads * dh * (dh if kind == "mlstm" else 4)
    return w_bytes + kv / chips


def analyze(rec: dict) -> dict:
    chips = 1
    for d in rec["mesh"]:
        chips *= d
    flops_dev = rec["cost"]["flops"] or 0.0          # per-device (SPMD module)
    bytes_dev = rec["cost"]["bytes_accessed"] or 0.0
    wire = rec["collectives"]["total_wire_bytes"]    # per-device program
    corr = 0.0
    if rec.get("unrolled"):
        corr = _inner_scan_correction(rec["arch"], rec["shape"], chips)
        flops_dev += corr
    compute_t = flops_dev / PEAK_FLOPS
    hbm_model = model_hbm_bytes(rec["arch"], rec["shape"], chips)
    memory_t = hbm_model / HBM_BW                 # analytic TPU HBM model
    memory_hlo_t = bytes_dev / HBM_BW             # CPU-fusion upper bound
    coll_t = wire / LINK_BW
    terms = {"compute": compute_t, "memory": memory_t, "collective": coll_t}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["shape"])
    useful = mf / max(flops_dev * chips, 1e-30)
    step_t = max(terms.values())
    ideal_t = mf / (chips * PEAK_FLOPS)
    return dict(
        arch=rec["arch"], shape=rec["shape"],
        mesh="x".join(str(d) for d in rec["mesh"]),
        compute_s=compute_t, memory_s=memory_t, collective_s=coll_t,
        memory_hlo_s=memory_hlo_t,
        dominant=dominant, model_flops=mf, hlo_flops_global=flops_dev * chips,
        inner_scan_corr_flops=corr,
        useful_ratio=useful,
        roofline_fraction=ideal_t / max(step_t, 1e-30),
        trip_corrected=bool(rec.get("unrolled")),
        memory_gib=dict(rec["memory"]),
    )


def load_all(out_dir: str = "results/dryrun", single_pod_only: bool = True):
    """Prefer unrolled (trip-count-exact) records per (arch, shape, mesh)."""
    best: dict[tuple, dict] = {}
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        rec = json.load(open(path))
        if rec.get("skipped") or not rec.get("ok"):
            continue
        if single_pod_only and rec.get("multi_pod"):
            continue
        key = (rec["arch"], rec["shape"], rec.get("multi_pod", False))
        if key in best and best[key].get("unrolled") and not rec.get("unrolled"):
            continue
        best[key] = rec
    return [analyze(r) for r in best.values()]


def markdown_table(out_dir: str = "results/dryrun") -> str:
    lines = [
        "| arch | shape | mesh | compute s | memory s | collective s | "
        "dominant | useful ratio | roofline frac |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in sorted(load_all(out_dir), key=lambda r: (r["arch"], r["shape"])):
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.3e} | {r['memory_s']:.3e} "
            f"| {r['collective_s']:.3e} | {r['dominant']} "
            f"| {r['useful_ratio']:.3f} | {r['roofline_fraction']:.3f} |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Fused query-path roofline (DESIGN.md Sec. 11)
#
# The per-(query,table)-row candidate pipeline — bucket gather -> score ->
# top-m — has a closed-form byte/FLOP model.  The staged path materialises
# the [r, P*KC, D] gather in HBM (write + re-read by the scorer) on top of
# the payload gather itself; the fused mega-kernel streams each bucket
# block through VMEM exactly once.  Bit-packed hamming payloads shrink the
# dominant payload term by 4*D / (4*ceil(k*L/32)).
# ---------------------------------------------------------------------------

QUERY_PEAKS = {
    # per-host peaks for placing the query kernel on a roofline; the cpu
    # numbers are order-of-magnitude for a multithreaded XLA host and exist
    # so roofline_frac stays meaningful in CI, not as a precise target
    "cpu": dict(flops=2.0e11, bw=3.0e10),
    "tpu_v5_lite": dict(flops=PEAK_FLOPS, bw=HBM_BW),
}


def _query_peaks(kind: str | None = None) -> dict:
    """Peaks of this device kind; a kind missing from the table is an
    error, never another device's numbers."""
    from repro.kernels import autotune

    kind = kind or autotune.device_kind()
    if kind not in QUERY_PEAKS:
        raise KeyError(f"no roofline peaks for device kind {kind!r}; "
                       f"known: {sorted(QUERY_PEAKS)}")
    return QUERY_PEAKS[kind]


def query_model(*, r: int, p: int, kc: int, payload_bytes: int, m: int,
                score: str = "dot", fused: bool = True,
                kind: str | None = None) -> dict:
    """Analytic bytes/FLOPs/time for one query-path batch.

    r probe rows (queries x tables), p probes each, kc candidate slots per
    bucket, payload_bytes per slot (4*D for f32 dot, 4*ceil(k*L/32) for
    packed hamming).
    """
    q_bytes = r * payload_bytes
    pay = float(r) * p * kc * payload_bytes   # bucket payload gather
    ids = float(r) * p * kc * 4               # candidate id words
    outs = r * m * 8                          # top-m ids + scores
    if fused:
        bytes_total = pay + ids + q_bytes + outs
    else:
        # gather materialises in HBM (write) and the scorer re-reads it
        bytes_total = 3.0 * pay + 2.0 * ids + q_bytes + outs
    lanes = payload_bytes / 4.0               # f32 dims or uint32 words
    if score == "dot":
        flops = 2.0 * r * p * kc * lanes
    else:
        flops = 16.0 * r * p * kc * lanes     # xor + SWAR popcount ops/word
    pk = _query_peaks(kind)
    t_mem = bytes_total / pk["bw"]
    t_comp = flops / pk["flops"]
    return dict(bytes=bytes_total, flops=flops, t_mem=t_mem, t_comp=t_comp,
                t_model=max(t_mem, t_comp),
                bound="memory" if t_mem >= t_comp else "compute")


def _bench(f, *args, reps=3):
    import time as _time

    import jax

    out = f(*args)
    jax.block_until_ready(out)
    t0 = _time.time()
    for _ in range(reps):
        out = f(*args)
    jax.block_until_ready(out)
    return (_time.time() - t0) / reps * 1e6


def _query_shapes(smoke: bool):
    # k=12, L=4 -> 2 packed words vs 128 f32 payload dims; the smoke
    # shape is the smallest where the packed-payload memory win is still
    # visible over dispatch overhead on a CPU host
    if smoke:
        return dict(t=2, nb=128, c=32, d=128, r=64, p=6, m=10, k=12, L=4)
    return dict(t=4, nb=256, c=64, d=128, r=128, p=8, m=10, k=12, L=4)


def _query_inputs(s: dict):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(11)
    tnb = s["t"] * s["nb"]
    ids = rng.integers(0, 10_000, size=(tnb, s["c"])).astype(np.int32)
    ids[rng.random(ids.shape) < 0.3] = -1
    pay = rng.standard_normal((tnb, s["c"], s["d"])).astype(np.float32)
    pay[ids < 0] = 0.0
    w = -(-(s["k"] * s["L"]) // 32)
    payw = rng.integers(0, 2**32, size=(tnb, s["c"], w), dtype=np.uint32)
    payw[ids < 0] = 0
    q = rng.standard_normal((s["r"], s["d"])).astype(np.float32)
    qw = rng.integers(0, 2**32, size=(s["r"], w), dtype=np.uint32)
    fb = rng.integers(0, tnb, size=(s["r"], s["p"])).astype(np.int32)
    meta = np.stack(
        [np.full(s["r"], (1 << s["p"]) - 1, np.int32),
         np.full(s["r"], -1, np.int32)], axis=1)
    return {k: jnp.asarray(v) for k, v in dict(
        ids=ids, pay=pay, payw=payw, q=q, qw=qw, fb=fb, meta=meta).items()}


def query_rows(smoke: bool = False):
    """Measured staged/fused query-path rows with model roofline fractions.

    On CPU hosts the fused Pallas rows run in interpret mode (correctness
    path, labelled as such); the staged rows are jit'd XLA, so the
    packed-hamming-over-dot ratio is a real measured speedup.
    """
    from functools import partial

    import jax

    from repro.kernels import ops, ref

    s = _query_shapes(smoke)
    v = _query_inputs(s)
    w = v["payw"].shape[-1]
    shared = (f"r={s['r']};P={s['p']};KC={s['c']};D={s['d']};"
              f"W={w};m={s['m']}")

    staged_dot = jax.jit(partial(ref.fused_query_ref, m=s["m"]))
    staged_ham = jax.jit(partial(ref.fused_query_ref, m=s["m"],
                                 score="hamming"))
    us_dot = _bench(staged_dot, v["ids"], v["pay"], v["q"], v["fb"],
                    v["meta"])
    us_ham = _bench(staged_ham, v["ids"], v["payw"], v["qw"], v["fb"],
                    v["meta"])

    def frac(us, *, payload_bytes, score, fused):
        mdl = query_model(r=s["r"], p=s["p"], kc=s["c"],
                          payload_bytes=payload_bytes, m=s["m"],
                          score=score, fused=fused)
        return mdl["t_model"] * 1e6 / max(us, 1e-9), mdl["bound"]

    f_dot, b_dot = frac(us_dot, payload_bytes=4 * s["d"], score="dot",
                        fused=False)
    f_ham, b_ham = frac(us_ham, payload_bytes=4 * w, score="hamming",
                        fused=False)
    out = [
        (f"roofline/query_staged_dot_{s['r']}r", us_dot,
         f"roofline_frac={f_dot:.3f};bound={b_dot};{shared}"),
        (f"roofline/query_staged_hamming_{s['r']}r", us_ham,
         f"packed_over_dot={us_dot / us_ham:.3f}x;"
         f"roofline_frac={f_ham:.3f};bound={b_ham};{shared}"),
    ]

    fused_fn = partial(ops.fused_query, m=s["m"])
    us_f = _bench(lambda *a: fused_fn(*a), v["ids"], v["pay"], v["q"],
                  v["fb"], v["meta"], reps=1 if smoke else 2)
    f_f, b_f = frac(us_f, payload_bytes=4 * s["d"], score="dot", fused=True)
    mode = "interpret" if jax.default_backend() == "cpu" else "compiled"
    out.append(
        (f"roofline/query_fused_dot_{s['r']}r", us_f,
         f"fused_over_staged={us_dot / us_f:.3f}x;mode={mode};"
         f"roofline_frac={f_f:.3f};bound={b_f};{shared}"))
    return out


def _routed_query_inputs(s: dict):
    """The routed (post-all_to_all) block shape: n*cap rows — 4x the
    1-node row count here — where a fill fraction of rows carries probe
    word 0 (the mesh send buffers pad to capacity; overflow/fill rows
    reach the kernel masked-out, not absent)."""
    import jax.numpy as jnp
    import numpy as np

    sr = dict(s, r=4 * s["r"])
    v = _query_inputs(sr)
    meta = np.asarray(v["meta"]).copy()
    meta[3 * s["r"]:, 0] = 0  # fill rows: no valid probes
    return sr, {**v, "meta": jnp.asarray(meta)}


def sweep_fused(write_cache: bool = True, smoke: bool = False,
                routed: bool = False):
    """(TB, KC) autotune sweep for the fused query kernel on this host.

    Times ops.fused_query across a block-shape grid on the representative
    query-path shape and records the winner in the autotune cache keyed by
    device kind (kernels/autotune.py), so runtime dispatch picks it up.
    With ``routed=True`` the sweep runs the routed mesh stage's block
    shape instead — n*cap rows with a fill-row tail — and records the
    winner under "fused_query_routed", the key the mesh dispatch consults.
    """
    from functools import partial

    from repro.kernels import autotune, ops

    if routed:
        s, v = _routed_query_inputs(_query_shapes(smoke))
        tune_op = "fused_query_routed"
    else:
        s = _query_shapes(smoke)
        v = _query_inputs(s)
        tune_op = "fused_query"
    grid_tb = (4, 8) if smoke else (4, 8, 16)
    grid_kc = (8, 16) if smoke else (8, 16, 32, 64)
    best, best_us = None, float("inf")
    for tb in grid_tb:
        for kc in grid_kc:
            fn = partial(ops.fused_query, m=s["m"], tb=tb, kc=kc)
            us = _bench(lambda *a: fn(*a), v["ids"], v["pay"], v["q"],
                        v["fb"], v["meta"], reps=1 if smoke else 2)
            print(f"# sweep {tune_op} tb={tb} kc={kc}: {us:.0f}us")
            if us < best_us:
                best, best_us = dict(tb=tb, kc=kc), us
    path = autotune.put(tune_op, best) if write_cache else None
    return path, best, best_us


def rows(out_dir: str = "results/dryrun", smoke: bool = False):
    out = []
    for r in load_all(out_dir):
        out.append((
            f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}",
            max(r["compute_s"], r["memory_s"], r["collective_s"]) * 1e6,
            f"dom={r['dominant']};comp_s={r['compute_s']:.2e};"
            f"mem_s={r['memory_s']:.2e};coll_s={r['collective_s']:.2e};"
            f"useful={r['useful_ratio']:.3f};"
            f"roofline_frac={r['roofline_fraction']:.3f}"))
    out.extend(query_rows(smoke=smoke))
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / single rep (CI)")
    ap.add_argument("--sweep", action="store_true",
                    help="run the (TB, KC) autotune sweep and cache the "
                         "winner for this device kind")
    args = ap.parse_args()
    if args.sweep:
        for routed in (False, True):
            path, best, best_us = sweep_fused(smoke=args.smoke,
                                              routed=routed)
            op = "fused_query_routed" if routed else "fused_query"
            print(f"# autotune winner {op} {best} ({best_us:.0f}us)"
                  f" -> {path}")
    for name, us, derived in query_rows(smoke=args.smoke):
        print(f"{name},{us:.2f},{derived}")
    table = markdown_table()
    if table.count("\n") > 1:
        print(table)
