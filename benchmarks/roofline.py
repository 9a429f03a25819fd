"""Roofline analysis from the dry-run artifacts (deliverable (g)).

For every (arch x shape x mesh) cell this derives the three terms:

  compute    = HLO_FLOPs_per_device / peak_FLOPs
  memory     = HLO_bytes_per_device / HBM_bw
  collective = collective_bytes_per_device / link_bw    (1 link, conservative)

with the peaks of the chip the dry run targets (``PEAKS[DRYRUN_KIND]``).

HLO_FLOPs / bytes / collective bytes come from the loop-aware parse of the
compiled partitioned HLO (repro.launch.hlo_analysis) — XLA's own
cost_analysis counts while bodies once and is reported alongside as "raw".

Also reported per cell: MODEL_FLOPS (6·N_active·D train / 2·N_active·D
inference), the MODEL_FLOPS/HLO_FLOPs usefulness ratio, the dominant term,
and a one-line action that would move it.

Usage:
  PYTHONPATH=src python -m benchmarks.roofline [--dryrun-dir results/dryrun]
      [--format md|csv]
  PYTHONPATH=src python -m benchmarks.roofline --kernels [--json kernels.json]

``--kernels`` runs the stage-A kernel sweep instead: fused Pallas
traversal round (kernels.fused_traversal) vs the unfused op chain
(best_unexpanded + filter masks + ADC + frontier insert), checked
bitwise against the jnp reference twin and placed against the roofline
(ADC contraction FLOPs vs the VMEM-resident working set).  Emits
``fused_parity`` / ``fused_speedup`` / ``fused_compiled`` contract rows
for the nightly job.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  A device
# missing here is an error (``peaks``), never a default.
#   "TPU v5 lite" = TPU v5e.  Source: Google Cloud documentation, "TPU v5e":
#   197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip
#   (4 links -> 50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
# the dry run (repro.launch.dryrun) lowers its cells for a v5e mesh
DRYRUN_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of this kind: flops (bf16 FLOP/s), hbm_bw and
    link_bw (B/s).  Raises ValueError for a kind with no published row."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; add a "
            f"row with its source to benchmarks/roofline.py PEAKS "
            f"(known: {sorted(PEAKS)})"
        ) from None


def _param_split(cfg):
    """(dense_params, routed_expert_params) — EP shards only the latter."""
    total = cfg.param_count()
    if cfg.n_experts == 0:
        return total, 0
    moe_layers = sum(1 for k in cfg.layer_kinds if k == "moe")
    experts = moe_layers * cfg.n_experts * 3 * cfg.d_model * cfg.moe_d_ff
    return total - experts, experts


def model_bytes_per_device(rep: dict, variants: set | None = None) -> float:
    """Analytic HBM-traffic model (fusion-independent cross-check).

    train    — replicated-compute layers: each chip reads full gathered
               bf16 weights ~4x (fwd, remat-fwd, dgrad, wgrad); EP experts
               1/16; optimizer rw at the ZeRO shard; stored activations.
    prefill  — one weight pass + activation stream + emitted KV.
    decode   — TP weight shard (1/16) + this chip's KV-cache slice.
    """
    from repro.configs import get_config
    from repro.configs.base import ALL_SHAPES

    variants = variants or set()
    if rep["arch"].startswith("gateann"):
        return rep.get("hbm_bytes_per_device", 0.0)
    cfg = get_config(rep["arch"])
    shape = next(s for s in ALL_SHAPES if s.name == rep["shape"])
    n_dev = rep["n_devices"]
    tp = 16
    dense_p, expert_p = _param_split(cfg)
    d = cfg.d_model
    # int8 KV: 1 B codes + f32 scale per (slot, kv head) => ~0.53x of bf16
    kv_factor = (1.0 + 4.0 / cfg.head_dim) / 2.0 if "kv_int8" in variants else 1.0
    w_factor = 0.52 if "w_int8" in variants else 1.0  # int8 + per-channel scales

    def cache_bytes_total(batch, length):
        total = 0
        for kind, win in zip(cfg.layer_kinds, cfg.layer_windows):
            if kind in ("attn", "moe"):
                l_eff = min(win, length) if win else length
                total += batch * l_eff * cfg.n_kv_heads * cfg.head_dim * 2 * 2 * kv_factor
            elif kind == "rglru":
                total += batch * (cfg.lru_width or d) * 4
            elif kind in ("mlstm", "slstm"):
                total += batch * 2 * d * max(cfg.head_dim, 1) // 64 * 4
        return total

    if shape.kind == "train":
        b_loc = shape.global_batch / (n_dev / tp)
        t_loc = shape.seq_len / tp
        w = 4 * 2 * (dense_p + expert_p / tp)
        opt = 2 * 12 * cfg.param_count() / n_dev
        act = 40 * b_loc * t_loc * d * 2 * cfg.n_layers
        return w + opt + act
    if shape.kind == "prefill":
        b_loc = shape.global_batch / (n_dev / tp)
        t_loc = shape.seq_len / tp
        w = 2 * (dense_p + expert_p / tp)
        act = 20 * b_loc * t_loc * d * 2 * cfg.n_layers
        kv = cache_bytes_total(shape.global_batch, shape.seq_len) / n_dev
        return w + act + kv
    # decode / long
    w = 2 * (dense_p + expert_p) / tp * w_factor
    kv = cache_bytes_total(shape.global_batch, shape.seq_len) / n_dev
    return w + kv


def analytic_collective_bytes(rep: dict, variants: set | None = None) -> dict:
    """Variant-aware collective model with *logical* dtypes.

    The CPU backend float-normalizes bf16 to f32 before partitioning
    (verified on a micro-case, EXPERIMENTS §Perf), so parsed HLO bytes
    overstate bf16 traffic 2x and cannot show bf16-vs-fp32 deltas.  This
    model reproduces the HLO's op *structure* (which the parse does
    verify: per-layer forward+backward weight gathers, K/V gathers, one
    full-gradient reduction per layer, MoE dispatch/combine) with the
    dtype each tensor logically carries.

    Ring traffic per device: all-gather/reduce-scatter ~ bytes x (g-1)/g;
    all-reduce ~ 2x that.
    """
    from repro.configs import get_config
    from repro.configs.base import ALL_SHAPES

    variants = variants or set()
    if rep["arch"].startswith("gateann"):
        return {"total": rep.get("collective_bytes_total", 0.0)}
    cfg = get_config(rep["arch"])
    shape = next(s for s in ALL_SHAPES if s.name == rep["shape"])
    n_dev = rep["n_devices"]
    tp = 16
    dense_p, expert_p = _param_split(cfg)
    cast_early = "cast_early" in variants
    grad_shard = "grad_shard" in variants
    w_bytes = 2 if cast_early else 4  # gathered compute weights
    g_bytes = 2 if cast_early else 4  # reduced gradients
    ring = lambda b, g: b * (g - 1) / max(g, 1)

    out = {}
    if shape.kind == "train":
        b_loc = shape.global_batch / (n_dev / tp)
        # per-layer weight gathers: fwd + remat-recomputed bwd (2 passes)
        out["ag_params"] = 2 * ring((dense_p + expert_p / tp) * w_bytes, n_dev)
        # K/V all-gather over `model` per attn layer, fwd + bwd recompute
        n_attn = sum(1 for k in cfg.layer_kinds if k in ("attn", "moe"))
        kv = b_loc * shape.seq_len * cfg.n_kv_heads * cfg.head_dim * 2 * 2
        out["ag_kv"] = 2 * ring(kv * n_attn, tp)
        # gradient reduction: all-reduce (2x) vs reduce-scatter (1x).
        # Expert grads are born EP-sharded (verified in HLO: group=16
        # reductions) — they reduce over `data` only at 1/tp size.
        red = ring(dense_p * g_bytes, n_dev) + ring(
            (expert_p / tp) * g_bytes, n_dev // tp)
        out["grad_reduce"] = red if grad_shard else 2 * red
        # MoE dispatch/combine all-to-alls (bf16 tokens), fwd + bwd
        n_moe = sum(1 for k in cfg.layer_kinds if k == "moe")
        if n_moe:
            tok = b_loc * (shape.seq_len / tp) * cfg.d_model * 2
            out["moe_a2a"] = 2 * 2 * 2 * ring(tok * n_moe, tp)
        if rep.get("multi_pod"):
            out["pod_allreduce"] = 2 * ring(cfg.param_count() * g_bytes / (n_dev // 2), 2)
    elif shape.kind == "prefill":
        out["ag_params"] = ring((dense_p + expert_p / tp) * 2, n_dev)
        n_attn = sum(1 for k in cfg.layer_kinds if k in ("attn", "moe"))
        b_loc = shape.global_batch / (n_dev / tp)
        kv = b_loc * shape.seq_len * cfg.n_kv_heads * cfg.head_dim * 2 * 2
        out["ag_kv"] = ring(kv * n_attn, tp)
    else:  # decode: per-layer activation psums (tiny) + distributed softmax
        b_loc = max(shape.global_batch / (n_dev / tp), 1)
        per_layer = b_loc * (cfg.d_model + cfg.n_heads * cfg.head_dim) * 4 * 4
        out["act_psums"] = 2 * per_layer * cfg.n_layers
    out["total"] = sum(v for k, v in out.items())
    return out


def model_flops_per_device(rep: dict) -> float:
    from repro.configs import get_config
    from repro.configs.base import ALL_SHAPES

    if rep["arch"].startswith("gateann"):
        return 0.0
    cfg = get_config(rep["arch"])
    shape = next(s for s in ALL_SHAPES if s.name == rep["shape"])
    n_act = cfg.active_param_count()
    n_dev = rep["n_devices"]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens / n_dev
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens / n_dev
    # decode: one token per sequence
    return 2.0 * n_act * shape.global_batch / n_dev


def suggestion(dom: str, rep: dict) -> str:
    kind = rep.get("layout", "")
    if dom == "collective":
        return "cut gather volume (reshard params/KV; overlap behind layer compute)"
    if dom == "memory":
        if kind in ("decode", "long"):
            return "quantize weights+KV (int8) or raise per-chip batch to amortize weight reads"
        return "reduce remat traffic / fuse optimizer update"
    return "compute-bound: improve MFU (block-causal attention, remat policy)"


def analyze_cell(rep: dict) -> dict:
    pk = peaks(DRYRUN_KIND)
    t_c = rep["flops_per_device"] / pk["flops"]
    # memory: min(parsed-HLO bytes, analytic model) — the parse is an upper
    # bound because CPU-backend fusion is weaker than TPU's (EXPERIMENTS §R)
    hlo_m = rep.get("hbm_bytes_per_device", 0.0) / pk["hbm_bw"]
    ana_m = model_bytes_per_device(rep) / pk["hbm_bw"]
    t_m = min(hlo_m, ana_m) if ana_m else hlo_m
    # dtype-corrected collective model (CPU HLO is f32-normalized); the
    # HLO parse bounds it from above and verifies the op structure.
    t_x_model = analytic_collective_bytes(rep)["total"] / pk["link_bw"]
    t_x_hlo = rep.get("collective_bytes_total", 0.0) / pk["link_bw"]
    t_x = min(t_x_model, t_x_hlo) if t_x_model else t_x_hlo
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    dom = max(terms, key=terms.get)
    mf = model_flops_per_device(rep)
    bound = max(terms.values())
    return {
        "arch": rep["arch"],
        "shape": rep["shape"],
        "mesh": "x".join(map(str, rep["mesh"])),
        "t_compute_s": t_c,
        "t_memory_s": t_m,
        "t_memory_hlo_s": hlo_m,
        "t_memory_analytic_s": ana_m,
        "t_collective_s": t_x,
        "t_collective_hlo_s": t_x_hlo,
        "bottleneck": dom,
        "model_flops_per_dev": mf,
        "useful_ratio": (mf / rep["flops_per_device"]) if rep["flops_per_device"] else 0.0,
        "roofline_fraction": (t_c / bound) if bound else 0.0,
        "mfu_bound": (mf / pk["flops"] / bound) if bound and mf else 0.0,
        "suggestion": suggestion(dom, rep),
    }


# ---------------------------------------------------------------------------
# --kernels: fused vs unfused stage-A traversal round
# ---------------------------------------------------------------------------


def _kernel_round_state(b, l, w, m, c, k, n, seed=0):
    """Random mid-search round state (frontier + candidate batch)."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    fid = rng.choice(n, size=(b, l), replace=False if l <= n else True).astype(np.int32)
    fid[:, l // 2:] = -1  # half the frontier dead, like a mid-search round
    fd = np.where(fid >= 0, rng.random((b, l)).astype(np.float32) * 8,
                  np.float32(3.4e38))
    fexp = (rng.random((b, l)) < 0.4) & (fid >= 0)
    fpass = rng.random((b, l)) < 0.6
    nid = rng.integers(-1, n, size=(b, m)).astype(np.int32)
    ncodes = rng.integers(0, k, size=(b, m, c)).astype(np.int32)
    npass = rng.random((b, m)) < 0.6
    lut = (rng.normal(size=(b, c, k)).astype(np.float32)) ** 2
    entry = fid[:, 0].copy()
    return tuple(
        jnp.asarray(x)
        for x in (fid, fd, fexp, fpass, nid, ncodes, npass, lut, entry)
    )


def _unfused_stage(state, width):
    """The op-chain stage A the kernel fuses: ADC reference + dedup/insert
    (stable argsort) + best-unexpanded select + mode masks — i.e. the jnp
    reference twin, which is exactly the unfused building blocks."""
    from repro.kernels import ref as kref

    return kref.fused_traversal_round_ref(*state, mode="gate", width=width)


def kernels_sweep(args) -> int:
    import jax
    import numpy as np

    from repro.kernels import fused_traversal as ft
    from repro.kernels.backend import supports_compiled_pallas

    dev = jax.devices()[0]
    # an unknown chip fails here, before the sweep; --no-roofline keeps
    # the parity and timing rows on a device with no published peaks
    peak = None if args.no_roofline else peaks(dev.device_kind)
    b, l, w = args.batch, args.search_l, args.beam
    r, r_max = args.degree, args.r_max
    c, k = args.pq_chunks, args.pq_k
    m = w * (r + r_max)
    n = 100_000
    state = _kernel_round_state(b, l, w, m, c, k, n)
    compiled = supports_compiled_pallas()

    fused = lambda: ft.fused_traversal_round(*state, mode="gate", width=w)
    unfused = jax.jit(lambda s: _unfused_stage(s, w))

    # parity: every output field of the fused kernel bitwise-equal to the
    # jnp reference twin (= the unfused op chain)
    got, want = fused(), unfused(state)
    parity = all(
        np.array_equal(np.asarray(getattr(got, f)), np.asarray(getattr(want, f)))
        for f in got._fields
    )

    def bench(fn):
        fn()[0].block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(args.repeats):
            out = fn()
        jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
        return (time.perf_counter() - t0) / args.repeats

    t_fused = bench(fused)
    t_unfused = bench(lambda: unfused(state))
    speedup = t_unfused / t_fused if t_fused > 0 else 0.0

    rows = [
        {"name": "fused_parity", "derived": 1.0 if parity else 0.0},
        {"name": "fused_speedup", "derived": speedup},
        {"name": "fused_compiled", "derived": 1.0 if compiled else 0.0},
        {"name": "fused_us", "derived": t_fused * 1e6},
        {"name": "unfused_us", "derived": t_unfused * 1e6},
    ]
    if peak is not None:
        # roofline placement: ADC one-hot contraction dominates FLOPs
        # (B·C·M·K MACs); the working set is the VMEM-resident round state
        flops = 2.0 * b * c * m * k
        bytes_rt = 4.0 * b * (
            l * 4 + m * (2 + c) + c * k  # frontier + candidates/codes + lut
        )
        t_c, t_m = flops / peak["flops"], bytes_rt / peak["hbm_bw"]
        rows += [
            {"name": "stage_flops", "derived": flops},
            {"name": "stage_bytes", "derived": bytes_rt},
            {"name": "stage_intensity", "derived": flops / bytes_rt},
            {"name": "stage_roofline_bound_us",
             "derived": max(t_c, t_m) * 1e6},
        ]
    print("| metric | value |")
    print("|---|---|")
    for row in rows:
        print(f"| {row['name']} | {row['derived']:.6g} |")
    print(
        f"# shapes: B={b} L={l} W={w} M={m} C={c} K={k} "
        f"device={dev.platform}/{dev.device_kind}x{len(jax.devices())} "
        f"mode={'compiled' if compiled else 'interpret'}"
    )
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows, "shape": {
                "b": b, "l": l, "w": w, "m": m, "c": c, "k": k,
                "platform": dev.platform, "device_kind": dev.device_kind,
                "device_count": len(jax.devices()),
            }}, f, indent=1)
    return 0 if parity else 1


def main():
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-dir", default="results/dryrun")
    ap.add_argument("--format", default="md", choices=["md", "csv"])
    ap.add_argument("--mesh", default="16x16", help="16x16 | 2x16x16 | all")
    ap.add_argument("--kernels", action="store_true",
                    help="run the fused-vs-unfused stage-A kernel sweep")
    ap.add_argument("--json", default="",
                    help="(--kernels) write contract rows to this JSON file")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--search-l", type=int, default=64)
    ap.add_argument("--beam", type=int, default=8)
    ap.add_argument("--degree", type=int, default=32)
    ap.add_argument("--r-max", type=int, default=16)
    ap.add_argument("--pq-chunks", type=int, default=8)
    ap.add_argument("--pq-k", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--no-roofline", action="store_true",
                    help="(--kernels) parity and timing rows only, for a "
                         "device with no published peaks (the CPU)")
    args = ap.parse_args()

    if args.kernels:
        sys.exit(kernels_sweep(args))

    rows = []
    for path in sorted(glob.glob(os.path.join(args.dryrun_dir, "*.json"))):
        with open(path) as f:
            rep = json.load(f)
        mesh = "x".join(map(str, rep["mesh"]))
        if args.mesh != "all" and mesh != args.mesh:
            continue
        rows.append(analyze_cell(rep))

    if args.format == "csv":
        cols = ["arch", "shape", "mesh", "t_compute_s", "t_memory_s",
                "t_collective_s", "bottleneck", "useful_ratio",
                "roofline_fraction", "mfu_bound"]
        print(",".join(cols))
        for r in rows:
            print(",".join(
                f"{r[c]:.4g}" if isinstance(r[c], float) else str(r[c]) for c in cols
            ))
        return

    print("| arch | shape | mesh | compute (s) | memory (s) | collective (s) "
          "| bottleneck | useful | roofline frac | MFU bound | next move |")
    print("|---|---|---|---|---|---|---|---|---|---|---|"[: -4] + "|")
    for r in rows:
        print(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['t_compute_s']:.3f} | {r['t_memory_s']:.3f} "
            f"| {r['t_collective_s']:.3f} | **{r['bottleneck']}** "
            f"| {r['useful_ratio']:.2f} | {r['roofline_fraction']:.2f} "
            f"| {r['mfu_bound']:.2f} | {r['suggestion']} |"
        )


if __name__ == "__main__":
    main()
