"""Device-side second-pass binning: value -> bin for the whole matrix on TPU.

The reference extracts features into bins with a parallel C++ pass over all
rows (ref: src/io/dataset_loader.cpp:246,327 ExtractFeaturesFromMemory under
OpenMP).  This host is single-core, so the NumPy per-feature `searchsorted`
pass costs ~68 s at 10M x 28 — the TPU replacement streams the raw float32
matrix to the device once and bucketizes every feature in one compiled
program (compare-and-count against the per-feature bound rows), writing the
uint8 bin matrix device-side.

Exactness: for float32 inputs the comparison `bound < v` in float64 is
EXACTLY equivalent to `floor32(bound) < v` in float32, where floor32 rounds
the float64 bound DOWN to the nearest float32 (any float32 v <= bound is
also <= floor32(bound), and bound < v implies floor32(bound) <= bound < v).
So the device path reproduces the host `np.searchsorted(bounds, v, 'left')`
bin codes bit-for-bit; it is only offered for float32 data (float64 inputs
keep the host pass, whose comparisons need the full mantissa).
"""

from __future__ import annotations

import functools

import numpy as np

from .binning import BIN_CATEGORICAL, MISSING_NAN


def bounds_to_f32_floor(bounds64: np.ndarray) -> np.ndarray:
    """Round float64 bin bounds DOWN to float32 (see module docstring)."""
    b64 = np.asarray(bounds64, np.float64)
    b32 = b64.astype(np.float32)
    over = b32.astype(np.float64) > b64
    if over.any():
        b32[over] = np.nextafter(b32[over], np.float32(-np.inf))
    return b32


def device_binnable(mappers, used_features, data_dtype, num_data: int,
                    min_cells: int = 28 << 20) -> bool:
    """Gate for the device second pass: float32 data, a matrix large
    enough to pay for the transfer and the program, numeric features
    only, uint8-range bins, and a TPU backend present.  Large is counted
    in cells, rows x features, as the host pass's cost is: the gate was
    set at 2^20 rows of 28 features and stays there, and 400,000 rows of
    2,000 features (27x the cells; 800M host `searchsorted`s at 47 ns)
    pass it where a row count refused them (PERF.md, PR 30)."""
    if (data_dtype != np.float32
            or num_data * len(used_features) < min_cells):
        return False
    for f in used_features:
        m = mappers[f]
        if m.bin_type == BIN_CATEGORICAL or m.num_bin > 256:
            return False
    import jax
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=1)
def _bucketize_program():
    import jax
    import jax.numpy as jnp

    def prog(x, bounds, nan_zero, nan_bin, chunk: int):
        """x [n_pad, F] f32 (n_pad % chunk == 0), bounds [F, Bm] f32
        (floored, +inf padded), nan_zero [F] bool, nan_bin [F] int32
        -> [F, n_pad] uint8."""
        n, F = x.shape
        xr = x.reshape(n // chunk, chunk, F)

        def step(_, xc):
            nan = jnp.isnan(xc)
            xz = jnp.where(nan & nan_zero[None, :], jnp.float32(0), xc)
            cnt = jnp.sum((bounds[None, :, :] < xz[:, :, None]),
                          axis=-1, dtype=jnp.int32)      # [chunk, F]
            out = jnp.where(nan & ~nan_zero[None, :], nan_bin[None, :], cnt)
            return _, out.astype(jnp.uint8).T            # [F, chunk]

        _, outs = jax.lax.scan(step, None, xr)           # [C, F, chunk]
        return jnp.transpose(outs, (1, 0, 2)).reshape(F, n)

    return jax.jit(prog, static_argnames=("chunk",), donate_argnums=(0,))


def bin_matrix_device(data: np.ndarray, mappers, used_features,
                      chunk: int = 1 << 16):
    """Bin `data[:, used_features]` on device; returns a DEVICE
    jax.Array [F_used, n] uint8 — the whole point is that the bin
    matrix never visits the host (callers needing host bins go through
    Dataset.binned_host()).  Caller must have passed the
    `device_binnable` gate (float32 numeric data) — except
    `num_data`/backend, which only guard profitability, not correctness
    (tests run this on CPU)."""
    import jax
    import jax.numpy as jnp

    n = data.shape[0]
    Fu = len(used_features)
    n_bounds = []
    for f in used_features:
        m = mappers[f]
        n_search = m.num_bin - (1 if m.missing_type == MISSING_NAN else 0)
        n_bounds.append(m.bin_upper_bound[:n_search - 1]
                        if n_search > 0 else np.empty(0))
    Bm = max(1, max(len(b) for b in n_bounds))
    bounds = np.full((Fu, Bm), np.inf, np.float32)
    nan_zero = np.empty(Fu, bool)
    nan_bin = np.empty(Fu, np.int32)
    for i, f in enumerate(used_features):
        m = mappers[f]
        bounds[i, :len(n_bounds[i])] = bounds_to_f32_floor(n_bounds[i])
        nan_zero[i] = m.missing_type != MISSING_NAN
        nan_bin[i] = m.num_bin - 1
    n_pad = (n + chunk - 1) // chunk * chunk
    x = data if data.shape[1] == Fu else data[:, used_features]
    x = np.ascontiguousarray(x, np.float32)
    if n_pad != n:
        x = np.concatenate([x, np.zeros((n_pad - n, Fu), np.float32)])
    out = _bucketize_program()(jax.device_put(x), jnp.asarray(bounds),
                               jnp.asarray(nan_zero), jnp.asarray(nan_bin),
                               chunk)
    return out[:, :n] if n != out.shape[1] else out


def pull_host(binned) -> np.ndarray:
    """Device [F, n] -> host np.ndarray, flattened device-side first: a
    [F, n] uint8 array is tiled (and padded) in HBM and a flat one is
    not.  Whether that still buys anything for the D2H copy from a local
    chip is not re-measured since bring-up."""
    import jax
    if not isinstance(binned, jax.Array):
        return np.asarray(binned)
    F, n = binned.shape
    flat = jax.jit(lambda a: a.reshape(-1))(binned)
    return np.asarray(flat).reshape(F, n)
