"""The LM training shapes this repository claims for one chip.

One definition, read by ``tests/test_lm_app.py`` (which traces and
lowers every mode at its real shape) and by ``chip_smoke.py`` (which
runs the widest of them through the LM CLI on the chip), so the shapes
that are tested and the shapes that are run cannot drift apart.
"""

from __future__ import annotations

#: the byte-LM base every mode widens
LM_BASE = dict(
    vocab=256, d_model=512, n_heads=8, n_layers=8, d_ff=2048,
    remat=True, compute_dtype="bfloat16",
)

#: the widest mode: 403M parameters, no remat (``mfu_modes``' last d2048 row)
WIDEST = "mfu_d2048_s2048_noremat"


def mfu_modes(base: dict = LM_BASE) -> list:
    """The utilization-push modes as ``(name, LMConfig kwargs, {seq,
    batch, spl} overrides)``.

    d1024: d_head 128 (n_heads 8), seq 4096 with the token count kept
    via batch 8 — attention drops to ~1/4 of step FLOPs; the noremat
    variant removes recompute (utilization counts USEFUL flops, so
    remat deflates it), b4 keeps activations ~2 GB. d2048 (~400M
    params, d_ff 8192): attention falls to ~1/6 of step FLOPs, so the
    matmul share sets utilization almost alone. The s2048 variants
    halve the attention share again at the same tokens/step; their
    noremat forms hold ~3.6 GB of activations at batch 4 (d2048) and
    ~6.5 GB at batch 8 (d1024)."""
    big = {**base, "d_model": 1024, "n_layers": 12, "d_ff": 4096}
    d2048 = {**base, "d_model": 2048, "n_heads": 16, "n_layers": 8,
             "d_ff": 8192}
    return [
        ("mfu_d1024_s4096", dict(attention="ring_flash", **big),
         {"seq": 4096, "batch": 8}),
        ("mfu_d1024_s4096_noremat",
         dict(attention="ring_flash", **{**big, "remat": False}),
         {"seq": 4096, "batch": 4}),
        ("mfu_d2048_s4096", dict(attention="ring_flash", **d2048),
         {"seq": 4096, "batch": 4, "spl": 4}),
        ("mfu_d2048_s2048", dict(attention="ring_flash", **d2048),
         {"seq": 2048, "batch": 8, "spl": 4}),
        (WIDEST,
         dict(attention="ring_flash", **{**d2048, "remat": False}),
         {"seq": 2048, "batch": 4, "spl": 4}),
        ("mfu_d1024_s2048_noremat_b8",
         dict(attention="ring_flash", **{**big, "remat": False}),
         {"seq": 2048, "batch": 8}),
    ]
