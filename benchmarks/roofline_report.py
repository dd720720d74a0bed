"""Aggregate dry-run cell JSONs into the roofline table (EXPERIMENTS.md),
plus a modeled SpMV kernel roofline."""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List

from repro.core.costmodel import V5E_HBM_BW, V5E_VPU_FLOPS

RESULTS = os.path.join(os.path.dirname(__file__), "results", "dryrun")

# TPU v5e single-core numbers for the kernel roofline live in
# repro.core.costmodel (V5E_HBM_BW, V5E_VPU_FLOPS) so the overlap selector
# and these cells share one machine description (modeled, like the dry-run
# cells: labeled, never presented as measurements).


def spmv_kernel_cell(
    rows_per_proc: int = 2 ** 21,
    k: int = 9,
    ghost: int = 2 * 4096,
    value_bytes: int = 8,
) -> Dict:
    """Modeled roofline of the ELL SpMV on a paper-scale fine level: the
    ELL streams, x read once and y written once.  Deterministic arithmetic
    — gated by ``benchmarks.compare``.
    """
    n = rows_per_proc
    flops = 2.0 * n * k
    hbm = n * k * (4 + value_bytes) + (n + ghost) * value_bytes \
        + n * value_bytes
    return {
        "hbm_bytes": hbm,
        "flops": flops,
        "intensity": flops / hbm,
        "time_s": max(hbm / V5E_HBM_BW, flops / V5E_VPU_FLOPS),
    }


def overlap_cell(
    rows_per_proc: int = 2 ** 21,
    k: int = 9,
    ghost: int = 2 * 4096,
    n_neighbors: int = 8,
    value_bytes: int = 8,
) -> Dict:
    """Modeled exchange/compute overlap on the paper-scale fine level.

    Exchange from the v5e postal model (DCI neighbors of a two-deep 2-D
    halo), local compute from the same roofline compute model the overlap
    selector uses; reports the exchange time left exposed by the split
    schedule and the fraction hidden.  Deterministic arithmetic.
    """
    from repro.core.costmodel import (
        exposed_exchange_seconds,
        hidden_fraction,
        modeled_fine_exchange_time,
        overlap_split_overhead,
        spmv_compute_time,
    )

    tx = modeled_fine_exchange_time(n_neighbors, ghost,
                                    value_bytes=value_bytes)
    tl = spmv_compute_time(rows_per_proc * k, rows_per_proc,
                           rows_per_proc + ghost, value_bytes=value_bytes)
    return {
        "exchange_s": tx,
        "local_s": tl,
        "exposed_s": exposed_exchange_seconds(tx, tl),
        "hidden_frac": hidden_fraction(tx, tl),
        "overhead_s": overlap_split_overhead(rows_per_proc,
                                             value_bytes=value_bytes),
    }


def kernel_rows():
    c = spmv_kernel_cell()
    out = [(
        "roofline/spmv_flat",
        c["time_s"] * 1e6,
        "kind=modeled-roofline"
        f"|hbm_gb={c['hbm_bytes'] / 1e9:.3f}"
        f"|intensity={c['intensity']:.4f}",
    )]
    ov = overlap_cell()
    out.append((
        "roofline/spmv_overlap",
        ov["exposed_s"] * 1e6,
        "kind=modeled-roofline"
        f"|tx_us={ov['exchange_s'] * 1e6:.3f}"
        f"|local_us={ov['local_s'] * 1e6:.3f}"
        f"|hidden_frac={ov['hidden_frac']:.4f}"
        f"|overhead_us={ov['overhead_s'] * 1e6:.3f}",
    ))
    return out


def load_cells(include_variants: bool = True) -> List[Dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(RESULTS, "*.json"))):
        base = os.path.basename(path)[:-5]
        parts = base.split("__")
        is_variant = len(parts) > 3
        if is_variant and not include_variants:
            continue
        with open(path) as f:
            d = json.load(f)
        d["_variant"] = "__".join(parts[3:]) if is_variant else ""
        cells.append(d)
    return cells


def rows():
    out = kernel_rows()
    for c in load_cells():
        tag = f"{c.get('arch')}/{c.get('shape')}/{c.get('mesh')}"
        if c.get("_variant"):
            tag += f"/{c['_variant']}"
        if c.get("status") == "skipped":
            out.append((f"roofline/{tag}", 0.0,
                        "kind=skip|" + c.get("reason", "")[:60]))
            continue
        if c.get("status") != "ok":
            out.append((f"roofline/{tag}", 0.0, "kind=ERROR"))
            continue
        extra = ""
        dci = c.get("ici_dci_bytes_per_device")
        if dci:
            extra = (f"|dci_bytes={dci['dci']:.3g}"
                     f"|ici_bytes={dci['ici']:.3g}")
        out.append((
            f"roofline/{tag}",
            c["step_s_lower_bound"] * 1e6,
            "kind=dryrun-roofline"
            f"|bottleneck={c['bottleneck']}"
            f"|compute_us={c['compute_s'] * 1e6:.0f}"
            f"|memory_us={c['memory_s'] * 1e6:.0f}"
            f"|collective_us={c['collective_s'] * 1e6:.0f}"
            f"|useful_flops={c['useful_flops_ratio']:.2f}"
            f"|fits_v5e={c.get('memory_analytic', {}).get('fits_16gb_v5e')}"
            + extra,
        ))
    return out


def markdown_table(include_variants: bool = False) -> str:
    lines = [
        "| arch | shape | mesh | compute (s) | memory (s) | collective (s) "
        "| bound | useful | fits v5e |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for c in load_cells(include_variants=include_variants):
        v = f" `{c['_variant']}`" if c.get("_variant") else ""
        if c.get("status") == "skipped":
            lines.append(
                f"| {c['arch']} | {c['shape']} | {c['mesh']}{v} | — | — | — "
                f"| *skip: sub-quadratic attention required* | — | — |"
            )
            continue
        if c.get("status") != "ok":
            lines.append(
                f"| {c.get('arch')} | {c.get('shape')} | {c.get('mesh')}{v} "
                f"| — | — | — | ERROR | — | — |"
            )
            continue
        fits = c.get("memory_analytic", {}).get("fits_16gb_v5e", "?")
        lines.append(
            f"| {c['arch']} | {c['shape']} | {c['mesh']}{v} "
            f"| {c['compute_s']:.4f} | {c['memory_s']:.4f} "
            f"| {c['collective_s']:.4f} | {c['bottleneck']} "
            f"| {c['useful_flops_ratio']:.2f} | {fits} |"
        )
    return "\n".join(lines)


if __name__ == "__main__":
    import sys
    print(markdown_table(include_variants="--variants" in sys.argv))
