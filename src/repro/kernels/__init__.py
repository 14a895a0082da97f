"""Pallas TPU kernels for the paper's compute hot-spot: SpMV.

spmv_csrk.py      — CSR-k kernel (8 SSR tiles per step, banded x-window)
spmv_sellcs.py    — SELL-C-σ kernel (8 C-row chunks per step, whole x)
spmv_segsum.py    — speculative segmented-sum kernel (whole x)
spmv_diahybrid.py — DIA-plane kernel of the diagonal hybrid
gather.py         — the shared one-hot gather / reduce they are built from
spmv_ell.py       — ELL baseline kernel
ops.py            — wrappers (execution mode from the platform);
ref.py            — pure-jnp oracles
"""
