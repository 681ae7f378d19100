"""The chip's peaks and the compulsory work of one CP-ALS sweep.

The work is counted from (nnz, shape, R) alone, so it reads the same
whatever implements the sweep: no padding, no layout, no kernel choice.
"""
from __future__ import annotations

# Published peaks per ``device_kind`` as JAX reports it. The bf16 compute
# peak is the highest the chip offers, so for the fp32 work counted here it
# can only make the least time smaller and the roofline share lower.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,   # bf16
        "bytes_per_s": 819e9,    # HBM
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device_kind {device_kind!r}: add its published "
            f"peaks to the table in bench/work.py (known: {sorted(PEAKS)})"
        ) from None


def sweep_work(nnz: int, shape, rank: int) -> tuple[float, float]:
    """(FLOP, bytes) that one sweep over every mode cannot avoid.

    Per mode n: 2*N*R FLOP per nonzero (the Hadamard product of the N-1
    gathered rows, the scale by the value, the add into the output); the
    nonzero stream read once, nnz*(N+1)*4 bytes; each input factor read
    once, sum_{w != n} I_w*R*4; the output written once, I_n*R*4.
    Per remap (one per mode), the stream read and written once,
    2*nnz*(N+1)*4.
    """
    n_modes = len(shape)
    stream = nnz * (n_modes + 1) * 4
    factors = sum(int(d) for d in shape) * rank * 4
    flops = n_modes * 2 * n_modes * rank * nnz
    nbytes = n_modes * (stream + factors) + n_modes * 2 * stream
    return float(flops), float(nbytes)


def sweep_least_seconds(nnz: int, shape, rank: int, device_kind: str,
                        chips: int = 1) -> float:
    """Least time ``chips`` chips could take for one sweep's compulsory
    work, shared evenly between them."""
    p = peaks(device_kind)
    flops, nbytes = sweep_work(nnz, shape, rank)
    return max(flops / p["flops_per_s"], nbytes / p["bytes_per_s"]) / chips
