"""§Roofline: three-term roofline per (arch x shape x mesh) from the compiled
dry-run artifacts (benchmarks/artifacts/dryrun*/...), plus a disk-kernel
section giving the SAME compute/memory terms to the search hot-path kernels
(page_scan / pq_adc / fused_page_rank) so the fused pipeline's position on
the roofline sits next to the model kernels'.

Terms (per device, seconds per step), priced on the device table shared
with the analytic model (repro.core.device_model.TPU_DEVICES): the entry of
the device_kind REPRO_TPU_DEVICE names (e.g. "TPU v5 lite"), else the
attached TPU's; there is no default chip:
  compute    = HLO_FLOPs / peak_FLOPs            (v5e: 197 TFLOP/s bf16)
  memory     = HLO_bytes / HBM_bw                (v5e: 819 GB/s)
  collective = collective_bytes / link_bw        (v5e: ~50 GB/s/link ICI)

HLO_FLOPs/bytes are trip-count-corrected per-device numbers from
repro.parallel.hloanalysis (XLA's cost_analysis counts loop bodies once).
NOTE the memory term is an upper bound on this container: the CPU backend
fuses far less than TPU, so elementwise temporaries that a TPU would keep in
registers/VMEM are counted as HBM traffic. MODEL_BYTES (analytic minimum:
params+states+saved activations+KV reads) brackets it from below.

MODEL_FLOPS = 6*N_active*D (train) / 2*N_active*D (inference) + attention
term; ratio MODEL_FLOPS/HLO_FLOPs exposes remat/redundant compute.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

from repro.core.device_model import tpu_device

ART = Path(__file__).resolve().parent / "artifacts"


def model_flops(cfg, shape, n_dev: int) -> float:
    """Useful FLOPs per device per step (PaLM-style accounting)."""
    n_act = cfg.active_param_count()
    if shape.mode == "train":
        toks = shape.tokens
        factor = 6.0
        s_ctx = shape.seq_len
    elif shape.mode == "prefill":
        toks = shape.tokens
        factor = 2.0
        s_ctx = shape.seq_len
    else:  # decode: one token per sequence
        toks = shape.global_batch
        factor = 2.0
        s_ctx = shape.seq_len          # attends over the full cache
    n_attn_layers = sum(1 for i in range(cfg.num_layers)
                        if cfg.is_attn_layer(i))
    # attention: 2 matmuls (QK^T, PV) x 2 dims x causal/decode factor
    if shape.mode == "decode":
        att = 4.0 * n_attn_layers * cfg.num_heads * cfg.head_dim * s_ctx * toks
    else:
        att = (2.0 * n_attn_layers * cfg.num_heads * cfg.head_dim
               * s_ctx * toks)  # x0.5 causal x ... (2 matmuls x 2 flops x 0.5)
        att *= 2.0 * 0.5 * (3 if shape.mode == "train" else 1)
    total = factor * n_act * toks + att
    return total / n_dev


def model_bytes(cfg, shape, n_dev: int, rec) -> float:
    """Analytic minimum HBM traffic per device per step (what a fused TPU
    program must move; the CPU-HLO `traffic_bytes` is an upper bound that
    counts every unfused elementwise temp + non-donated cache copies)."""
    p_dev = cfg.param_count() * 2 / n_dev          # bf16 shards
    from repro.models import transformer as T
    ns = T.num_stages(cfg)
    if shape.mode == "train":
        toks_dev = shape.tokens / n_dev
        act_saves = ns * toks_dev * cfg.d_model * 2     # bf16 carry per stage
        opt = p_dev * (1.0 if cfg.opt_state_dtype == "bfloat16" else 2.0) * 2
        # params: read fwd + read bwd-recompute + read+write update;
        # grads: write + read; act saves: write + read; opt: read + write
        return (p_dev * 4 + p_dev * 2 + act_saves * 2 + opt)
    if shape.mode == "prefill":
        toks_dev = shape.tokens / n_dev
        kv_write = (2 * sum(1 for i in range(cfg.num_layers)
                            if cfg.is_attn_layer(i))
                    * cfg.num_kv_heads * cfg.head_dim * toks_dev * 2)
        return p_dev + kv_write + toks_dev * cfg.d_model * 2 * ns
    # decode: params once + the full KV-cache/state read (+1 token write)
    cache_read = rec["memory"]["argument_bytes"] - p_dev
    return p_dev + max(cache_read, 0.0)


def load(mesh_tag: str, tag: str = ""):
    d = ART / (f"dryrun_{tag}" if tag else "dryrun") / mesh_tag
    rows = []
    for f in sorted(d.glob("*.json")):
        rows.append(json.loads(f.read_text()))
    return rows


def analyze(mesh_tag="single", tag=""):
    from repro.configs import get_config, get_shape
    dev = tpu_device(os.environ.get("REPRO_TPU_DEVICE"))
    out = []
    for rec in load(mesh_tag, tag):
        if not rec.get("ok"):
            out.append({"arch": rec["arch"], "shape": rec["shape"],
                        "error": rec.get("error", "?")})
            continue
        cfg = get_config(rec["arch"])
        shape = get_shape(rec["shape"])
        n_dev = rec["n_devices"]
        coll_bytes = sum(v for k, v in rec["collectives"].items()
                        if not k.endswith("_count"))
        t_comp = rec["flops"] / dev.peak_flops
        t_mem = rec["traffic_bytes"] / dev.hbm_bw
        t_coll = coll_bytes / dev.link_bw
        mf = model_flops(cfg, shape, n_dev)
        mb = model_bytes(cfg, shape, n_dev, rec)
        t_mem_model = mb / dev.hbm_bw
        # dominant term: compute (HLO, trip-corrected), memory (analytic
        # model; CPU-HLO traffic reported alongside as an upper bound),
        # collective (HLO, exact SPMD sizes)
        terms = {"compute": t_comp, "memory": t_mem_model,
                 "collective": t_coll}
        dom = max(terms, key=terms.get)
        bound = max(terms.values())
        # roofline fraction: the time the USEFUL work needs at hardware peak
        # (its compute at peak FLOPs, or its minimal traffic at peak BW)
        # over the modeled step bound — 1.0 = step runs as fast as its
        # useful work possibly allows
        useful = max(mf / dev.peak_flops, t_mem_model)
        out.append({
            "arch": rec["arch"], "shape": rec["shape"], "mesh": mesh_tag,
            "compute_s": f"{t_comp:.4f}",
            "memory_s_model": f"{t_mem_model:.4f}",
            "memory_s_hlo_ub": f"{t_mem:.4f}",
            "collective_s": f"{t_coll:.4f}",
            "dominant": dom,
            "model_flops_per_dev": f"{mf:.3e}",
            "hlo_flops_per_dev": f"{rec['flops']:.3e}",
            "useful_ratio": f"{mf / max(rec['flops'], 1e-9):.3f}",
            "roofline_fraction": f"{useful / max(bound, 1e-12):.3f}",
            "hbm_gib_per_dev": f"{(rec['memory']['argument_bytes'] + rec['memory']['temp_bytes']) / 2**30:.1f}",
        })
    return out


def disk_kernels(n_pages: int = 8, n_p: int = 8, d: int = 128, m: int = 16,
                 q: int = 32):
    """Analytic roofline terms for the disk-path search kernels, per beam
    step of `n_pages` pages — no artifacts needed (the kernels' FLOP/byte
    counts are closed-form in their shapes). The fused kernel's row is the
    two halves' work under ONE memory pass and one dispatch; its bound is
    max(compute, memory) instead of their sum, which is exactly the overlap
    the measured benchmark (benchmarks/fused_pipeline.py) checks."""
    dev = tpu_device(os.environ.get("REPRO_TPU_DEVICE"))
    recs = n_pages * n_p
    vec_bytes = recs * d * 4
    code_bytes = recs * m
    lut_bytes = m * 256 * q * 4
    out_bytes = recs * q * 4
    scan_flops = recs * q * (2 * d + 3)          # x2 - 2xq + q2 per pair
    adc_flops = recs * q * 2 * m * 256           # one-hot matmul form
    rows = []
    for name, flops, bytes_ in (
            ("page_scan", scan_flops, vec_bytes + q * d * 4 + out_bytes),
            ("pq_adc", adc_flops, code_bytes + lut_bytes + out_bytes),
            ("fused_page_rank", scan_flops + adc_flops,
             vec_bytes + code_bytes + q * d * 4 + lut_bytes + 2 * out_bytes)):
        t_c = dev.compute_s(flops)
        t_m = dev.memory_s(bytes_)
        fused = name == "fused_page_rank"
        bound = max(t_c, t_m) if fused else t_c + t_m
        rows.append({
            "kernel": name, "device": dev.name,
            "pages": n_pages, "n_p": n_p, "d": d, "M": m, "Q": q,
            "flops": f"{flops:.3e}", "bytes": f"{bytes_:.3e}",
            "intensity_flop_per_byte": f"{flops / bytes_:.1f}",
            "compute_us": f"{t_c * 1e6:.3f}",
            "memory_us": f"{t_m * 1e6:.3f}",
            "bound": ("compute" if t_c > t_m else "memory"),
            "step_us": f"{bound * 1e6:.3f}",
        })
    return rows


def main(argv=None):
    argv = argv or sys.argv[1:]
    tag = argv[argv.index("--tag") + 1] if "--tag" in argv else ""
    for mesh in ("single", "multi"):
        rows = analyze(mesh, tag)
        if not rows:
            continue
        cols = list(rows[0])
        print(f"== roofline ({mesh}) ==")
        print(",".join(cols))
        for r in rows:
            print(",".join(str(r.get(c, "")) for c in cols))
    rows = disk_kernels()
    cols = list(rows[0])
    print(f"== roofline (disk-path kernels, {rows[0]['device']}) ==")
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r.get(c, "")) for c in cols))
    return 0


if __name__ == "__main__":
    main()
