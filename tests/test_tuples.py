"""The row-gather primitive (core/tuples.py ``select_rows`` / ``take_rows``)
against numpy's own subscript: ``batch[mask]`` / ``batch[idx]`` is the
reference, kept here so the contract the engine leans on — same rows, same
order, same dtype, an owned C-contiguous writable array that aliases
nothing — is pinned for every layout and edge the engine meets."""

import numpy as np
import pytest

from windflow_tpu.core.tuples import Schema, select_rows, take_rows

#: packed 33 B (pipe_cb), packed 42 B (ysb_kf's events), an aligned layout
DTYPES = {
    "packed33": Schema(value=np.int64).dtype(),
    "packed42": Schema(ad_id=np.int64, event_type=np.int8,
                       revenue=np.int64).dtype(),
    "aligned": np.dtype([("key", np.int64), ("id", np.int64),
                         ("ts", np.int64), ("marker", np.bool_),
                         ("value", np.float32)], align=True),
}


def _filled(dtype, n, seed=0):
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, 256, n * dtype.itemsize, dtype=np.uint8)
    b = raw.view(dtype).copy()
    # bools and floats from random bytes compare badly (non-0/1 bools, NaN)
    for f in dtype.names:
        if dtype[f].kind in "bf":
            b[f] = rng.integers(0, 2, n)
    return b


def _masks(n, rng):
    some = rng.random(n) < 0.4
    one = np.zeros(n, dtype=bool)
    if n:
        one[n // 3] = True
    return {"some": some, "all_true": np.ones(n, dtype=bool),
            "all_false": np.zeros(n, dtype=bool), "single": one}


def _check_contract(out, want, src, src_before):
    assert out.dtype is src.dtype
    assert out.dtype.itemsize == src.dtype.itemsize
    assert out.dtype.fields == src.dtype.fields        # offsets included
    assert out.shape == want.shape
    # field by field: an aligned layout's padding bytes are nobody's data
    for f in src.dtype.names:
        assert np.array_equal(out[f], want[f]), f
    assert out.flags.owndata and out.base is None
    assert out.flags.c_contiguous and out.flags.writeable
    assert not np.shares_memory(out, src)
    if len(out):
        out["key"] += 1
        out[out.dtype.names[-1]] = 0
    assert src.tobytes() == src_before


@pytest.mark.parametrize("mask_kind",
                         ["some", "all_true", "all_false", "single"])
@pytest.mark.parametrize("layout", ["contiguous", "strided", "empty"])
@pytest.mark.parametrize("dt", list(DTYPES))
def test_select_rows_is_boolean_subscript(dt, layout, mask_kind):
    dtype = DTYPES[dt]
    base = _filled(dtype, 0 if layout == "empty" else 257)
    src = base[::2] if layout == "strided" else base
    assert src.flags.c_contiguous == (layout != "strided" or len(src) < 2)
    mask = _masks(len(src), np.random.default_rng(1))[mask_kind]
    want = src[mask].copy()
    before = src.tobytes()
    _check_contract(select_rows(src, mask), want, src, before)


@pytest.mark.parametrize("idx_kind",
                         ["permutation", "repeats", "empty", "negative"])
@pytest.mark.parametrize("layout", ["contiguous", "strided"])
@pytest.mark.parametrize("dt", list(DTYPES))
def test_take_rows_is_integer_subscript(dt, layout, idx_kind):
    dtype = DTYPES[dt]
    base = _filled(dtype, 200, seed=2)
    src = base[1::3] if layout == "strided" else base
    n = len(src)
    rng = np.random.default_rng(3)
    idx = {"permutation": rng.permutation(n),
           "repeats": rng.integers(0, n, 3 * n),
           "empty": np.zeros(0, dtype=np.int64),
           "negative": np.array([-1, 0, -n, n - 1])}[idx_kind]
    want = src[idx].copy()
    before = src.tobytes()
    _check_contract(take_rows(src, idx), want, src, before)


def test_take_rows_out_of_range_raises_like_subscript():
    b = _filled(DTYPES["packed33"], 8)
    with pytest.raises(IndexError):
        b[np.array([8])]
    with pytest.raises(IndexError):
        take_rows(b, np.array([8]))


def test_select_rows_takes_a_strided_mask_and_a_readonly_batch():
    b = _filled(DTYPES["packed33"], 64)
    b["marker"] = np.arange(64) % 3 == 0
    b.flags.writeable = False
    out = select_rows(b, b["marker"])          # the field view is strided
    assert out.tobytes() == b[b["marker"]].tobytes()
    assert out.flags.writeable and out.flags.owndata
