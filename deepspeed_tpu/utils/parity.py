"""Kernel-vs-reference agreement at the bf16 floor — the ONE tolerance rule
for comparisons made on a real TPU (``tests/conftest.py`` under
``DSTPU_TEST_TPU=1``, and ``chip_smoke.py``'s kernel phase).

Mosaic and XLA both execute fp32 matmuls as bf16 MXU passes but in
different reduction orders, so kernel-vs-oracle comparisons land at bf16
scale (measured 2026-07-31 on a v5e: max abs ~4e-3 on O(0.1) attention
outputs) — far looser than the CPU interpreter, where both paths are exact
fp32. The rule is bulk-tight / tail-tolerant: everything must sit within
the bf16 floor EXCEPT up to 1% of elements, which may reach 0.1 abs
(softmax-saturated rows and head_dim-128 reductions amplify tiny lse
rounding; worst measured case dk at d=128 causal: 0.72% / 0.086). A
mask/sign/logic regression flips tens of percent at O(1) magnitude and
still fails both prongs.
"""

from typing import Optional

import numpy as np

__all__ = ["BF16_RTOL", "BF16_ATOL", "bf16_mismatch"]

BF16_RTOL = 2e-2
BF16_ATOL = 5e-3

# Contiguous elements per tail-accounting window. Sized so legitimate
# per-ROW rounding tails pass (a softmax-saturated dk row at d=128 is 128
# contiguous bad elements = 1.6% of a window) while a corrupted kernel
# TILE (>= 128x128 = 16384 elements at ~100%) saturates whole windows.
_TAIL_BLOCK = 8192


def bf16_mismatch(actual, desired, rtol: float = BF16_RTOL,
                  atol: float = BF16_ATOL) -> Optional[str]:
    """``None`` when ``actual`` agrees with ``desired`` under the rule
    above, else a one-line description of how it does not."""
    a = np.asarray(actual, np.float64)
    d = np.asarray(desired, np.float64)
    if a.shape != d.shape:
        return f"shape {a.shape} != {d.shape}"
    if not (np.isfinite(a).all() and np.isfinite(d).all()):
        return "non-finite values"
    err = np.abs(a - d)
    bad = err > (atol + rtol * np.abs(d))
    if not bad.any():
        return None
    if bad.mean() > 0.01 or err[bad].max() > 0.1:
        return (f"{bad.mean():.3%} of elements outside rtol={rtol}/"
                f"atol={atol}, max abs err {err.max():.3g} (allowed: <= 1% "
                f"of elements, each <= 0.1 abs)")
    # Per-window tail accounting: the global 1% allowance must be
    # SCATTERED rounding noise, not one corrupted kernel tile — a
    # localized regression (e.g. a bad 128x128 block in a 16k-seq layout)
    # concentrates its errors in a contiguous run, so also cap the bad
    # fraction per _TAIL_BLOCK-element window at 5% (a legitimate
    # lse-rounding ROW at d=128 is 1.6% of a window; a corrupted tile
    # saturates windows). Limitation: corruption STRIDED across many
    # heads (64-element stripes every h*d elements) dilutes below this
    # cap — contiguous-window accounting can't see row structure.
    flat = bad.reshape(-1)
    pad = (-flat.size) % _TAIL_BLOCK
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, bool)])
    per_block = flat.reshape(-1, _TAIL_BLOCK).mean(axis=1)
    if per_block.max() > 0.05:
        return (f"clustered kernel-parity tail: block "
                f"{int(per_block.argmax())} has {per_block.max():.1%} "
                f"elements outside rtol={rtol}/atol={atol} (global tail "
                f"{bad.mean():.3%} <= 1% but localized — likely a "
                f"corrupted kernel tile, not rounding)")
    return None
