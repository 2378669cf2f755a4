import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ydf_tpu.ops.histogram import (
    StatColumn,
    _histogram_matmul,
    _histogram_segment,
    histogram,
    narrow_columns_per_slot,
    split_bf16,
)


def _ref_histogram(bins, slot, stats, L, B):
    n, F = bins.shape
    S = stats.shape[1]
    out = np.zeros((L, F, B, S), np.float64)
    for i in range(n):
        if slot[i] >= L:
            continue
        for f in range(F):
            out[slot[i], f, bins[i, f]] += stats[i]
    return out


@pytest.mark.parametrize("impl", ["segment", "matmul"])
def test_histogram_matches_reference(impl):
    rng = np.random.RandomState(0)
    n, F, L, B, S = 500, 4, 8, 16, 3
    bins = rng.randint(0, B, size=(n, F)).astype(np.uint8)
    slot = rng.randint(0, L + 1, size=n).astype(np.int32)  # L = inactive
    stats = rng.normal(size=(n, S)).astype(np.float32)
    got = histogram(
        jnp.asarray(bins), jnp.asarray(slot), jnp.asarray(stats),
        num_slots=L, num_bins=B, impl=impl,
    )
    want = _ref_histogram(bins, slot, stats, L, B)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["segment", "matmul"])
def test_histogram_chunking(impl):
    rng = np.random.RandomState(1)
    n, F, L, B, S = 1000, 2, 4, 8, 2
    bins = rng.randint(0, B, size=(n, F)).astype(np.uint8)
    slot = rng.randint(0, L, size=n).astype(np.int32)
    stats = rng.normal(size=(n, S)).astype(np.float32)
    a = histogram(jnp.asarray(bins), jnp.asarray(slot), jnp.asarray(stats),
                  num_slots=L, num_bins=B, impl=impl, chunk=128)
    b = histogram(jnp.asarray(bins), jnp.asarray(slot), jnp.asarray(stats),
                  num_slots=L, num_bins=B, impl="segment")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def _mixed_magnitudes(rng, shape, lo=-6.0, hi=6.0):
    """Normal f32 values of both signs whose magnitudes spread evenly
    over the decades 10**lo .. 10**hi."""
    return (
        rng.normal(size=shape) * 10.0 ** rng.uniform(lo, hi, size=shape)
    ).astype(np.float32)


@pytest.mark.parametrize("magnitude", [1e-6, 1e-3, 1.0, 1e3, 1e6, None])
def test_split_bf16_three_pieces_are_the_f32(magnitude):
    """Three bf16 pieces add up to the f32 value BIT for bit (8 + 8 + 8
    significand bits); two — the bf16x2 operand — leave 2^-17 of it
    behind. None = every decade from 1e-6 to 1e6 in one array."""
    rng = np.random.default_rng(7)
    x = (
        _mixed_magnitudes(rng, (20000, 3)) if magnitude is None
        else (rng.normal(size=(20000, 3)) * magnitude).astype(np.float32)
    )
    S = x.shape[1]
    got = split_bf16(jnp.asarray(x), 3)
    assert got.dtype == jnp.bfloat16 and got.shape == (x.shape[0], 3 * S)
    hi, mid, lo = (
        np.asarray(got[:, p * S:(p + 1) * S].astype(jnp.float32), np.float64)
        for p in range(3)
    )
    np.testing.assert_array_equal(
        (hi + mid + lo).astype(np.float32).view(np.int32), x.view(np.int32)
    )
    assert np.max(np.abs(hi + mid - x) / np.abs(x)) > 2.0 ** -19
    # Two pieces are the leading two of three.
    two = np.asarray(split_bf16(jnp.asarray(x), 2).astype(jnp.float32))
    np.testing.assert_array_equal(two[:, :S], hi)
    np.testing.assert_array_equal(two[:, S:], mid)


@pytest.mark.parametrize("chunk", [1 << 18, 1024])  # one chunk; four
@pytest.mark.parametrize("L", [1, 4, 16, 48])  # dots of 9; 36; 96 and 48; 3 x 144
def test_matmul_f32_keeps_every_bit_of_the_stats(L, chunk):
    """The f32 matmul histogram (one bf16 pass over three exact pieces)
    on real-valued stats of mixed magnitude sits as close to an f64
    oracle as the segment impl's f32 sums do, relative to the cell's
    magnitude Σ|stat| — and well inside what the two-piece bf16x2 mode
    leaves behind on the same input."""
    rng = np.random.default_rng(100 * L + chunk % 7)
    n, F, B, S = 4096, 3, 16, 3
    bins = rng.integers(0, B, (n, F)).astype(np.uint8)
    slot = rng.integers(0, L + 1, (n,)).astype(np.int32)  # L = trash
    stats = _mixed_magnitudes(rng, (n, S), -2.0, 2.0)
    want = np.zeros((L + 1, F, B, S))
    mag = np.zeros((L + 1, F, B, S))
    at = (slot[:, None], np.arange(F)[None, :], bins)
    np.add.at(want, at, stats[:, None, :].astype(np.float64))
    np.add.at(mag, at, np.abs(stats[:, None, :]).astype(np.float64))
    want, mag = want[:L], np.maximum(mag[:L], 1e-30)

    def gap(impl, quant):
        got = histogram(
            jnp.asarray(bins), jnp.asarray(slot), jnp.asarray(stats),
            num_slots=L, num_bins=B, impl=impl, quant=quant, chunk=chunk,
        )
        assert got.dtype == jnp.float32 and got.shape == want.shape
        return float(np.max(np.abs(np.asarray(got, np.float64) - want) / mag))

    matmul, segment = gap("matmul", "f32"), gap("segment", "f32")
    two_pieces = gap("matmul", "bf16x2")
    # f32 accumulation alone: a few ulp (2^-24) of the cell's magnitude.
    assert matmul <= max(2.0 * segment, 2.0 ** -21), (matmul, segment)
    assert matmul < 1e-6 < two_pieces, (matmul, two_pieces)
    assert two_pieces > 8.0 * matmul, (matmul, two_pieces)


def _impls_with_native():
    from ydf_tpu.ops import histogram_native

    impls = ["segment", "matmul", "pallas_interpret"]
    if histogram_native.available():
        impls.append("native")
    return impls


@pytest.mark.parametrize("n", [1000, 1024])  # 1000 % 256 != 0; 1024 exact
@pytest.mark.parametrize("chunk", [256])
def test_chunk_boundaries_bit_equal(n, chunk):
    """Every impl at a small explicit chunk — both with a ragged tail
    (n % chunk != 0) and at the exact-multiple edge — is BIT-equal to
    the unchunked segment oracle. Integer-valued stats make every
    partial sum exactly representable in f32, so accumulation order
    (scan chunks, per-thread blocks, dot tilings) cannot excuse a
    mismatch."""
    rng = np.random.default_rng(n)
    F, L, B, S = 5, 8, 16, 3
    bins = jnp.asarray(rng.integers(0, B, (n, F)), jnp.uint8)
    slot = jnp.asarray(rng.integers(0, L + 1, (n,)), jnp.int32)
    stats = jnp.asarray(
        rng.integers(-8, 9, (n, S)).astype(np.float32)
    )
    oracle = np.asarray(
        histogram(bins, slot, stats, num_slots=L, num_bins=B,
                  impl="segment", chunk=1 << 20)
    )
    for impl in _impls_with_native():
        got = np.asarray(
            histogram(bins, slot, stats, num_slots=L, num_bins=B,
                      impl=impl, chunk=chunk)
        )
        np.testing.assert_array_equal(got, oracle, err_msg=impl)


@pytest.mark.parametrize("quant", ["f32", "bf16x2", "int8"])
@pytest.mark.parametrize("L", [1, 2, 4, 8, 16, 24])  # 24: one piece a dot
def test_matmul_narrow_operand_at_every_width(L, quant):
    """`_histogram_matmul` itself, on the operand each quant mode hands
    it, is BIT-equal to the segment oracle at every width of its narrow
    operand: slot counts that fill no sublane tile (2, 4), that fill
    whole ones (8, 16), one slot, two dots a feature (16) and one piece
    a dot (24), over two whole chunks and a ragged tail, rows on the
    trash slot included. Whole-number stats whose cell sums stay under
    2^24 make both sides exact, so neither how the operand is built nor
    the order of the chunks can excuse a difference. The f32 values
    need all three bf16 pieces (18 bits)."""
    rng = np.random.default_rng(10 * L + len(quant))
    chunk, F, B, S = 256, 5, 64, 3
    n = 2 * chunk + 77
    bins = jnp.asarray(rng.integers(0, B, (n, F)), jnp.uint8)
    slot = jnp.asarray(rng.integers(0, L + 1, (n,)), jnp.int32)  # L = trash
    if quant == "f32":
        whole = rng.integers(-(2 ** 18) + 1, 2 ** 18, (n, S)) | 1
        stats = jnp.asarray(whole.astype(np.float32))
    elif quant == "bf16x2":  # high halves of 8 bits beside small residuals
        hi = rng.integers(-127, 128, (n, S)) * 256
        lo = rng.integers(-127, 128, (n, S))
        stats = jnp.asarray(np.concatenate([hi, lo], axis=1), jnp.bfloat16)
    else:
        stats = jnp.asarray(rng.integers(-127, 128, (n, S)), jnp.int8)
    want = np.asarray(_histogram_segment(bins, slot, stats, L, B))
    mass = _histogram_segment(bins, slot, jnp.abs(stats), L, B)
    assert float(jnp.max(mass)) < 2.0 ** 24  # every partial sum is exact
    got = np.asarray(_histogram_matmul(bins, slot, stats, L, B, chunk))
    assert got.shape == (L, F, B, stats.shape[1])
    assert np.any(want != 0)
    np.testing.assert_array_equal(got, want)


_SEVEN = (StatColumn(), StatColumn(), StatColumn(pieces=1))
_FOUR = (StatColumn(), StatColumn(same_as=2), StatColumn(pieces=1))


@pytest.mark.parametrize("columns", [_SEVEN, _FOUR], ids=["7", "4"])
@pytest.mark.parametrize("L", [1, 2, 4, 8, 16, 32])
def test_described_operand_sums_the_plain_ones_bits(L, columns):
    """`_histogram_matmul` told that the weight column is 0 or 1 (one
    bf16 piece: 7 columns a slot) and that the hessian column is the
    weight (not contracted: 4) returns the plain 9-column result BIT for
    bit, on real-valued gradients that need all three pieces, over two
    whole chunks and a ragged tail with rows of weight 0 and rows on
    the trash slot, at one dot a level (to 16 slots) and past it (32);
    and it sits as close to the segment impl as f32 sums do."""
    rng = np.random.default_rng(37 * L + len(columns))
    chunk, F, B = 256, 5, 32
    n = 2 * chunk + 77
    bins = jnp.asarray(rng.integers(0, B, (n, F)), jnp.uint8)
    slot = jnp.asarray(rng.integers(0, L + 1, (n,)), jnp.int32)  # L = trash
    w = (rng.random(n) < 0.85).astype(np.float32)
    g = _mixed_magnitudes(rng, (n,), -2.0, 2.0) * w
    h = w if columns is _FOUR else rng.random(n).astype(np.float32) * w
    stats = jnp.asarray(np.stack([g, h, w], axis=1))
    assert narrow_columns_per_slot(columns) == {_SEVEN: 7, _FOUR: 4}[columns]
    plain = np.asarray(_histogram_matmul(bins, slot, stats, L, B, chunk))
    got = np.asarray(
        _histogram_matmul(bins, slot, stats, L, B, chunk, columns)
    )
    assert got.shape == plain.shape == (L, F, B, 3) and np.any(plain != 0)
    assert got.tobytes() == plain.tobytes()
    through_jit = histogram(
        bins, slot, stats, num_slots=L, num_bins=B, impl="matmul",
        chunk=chunk, stat_columns=columns,
    )
    assert np.asarray(through_jit).tobytes() == plain.tobytes()
    segment = np.asarray(_histogram_segment(bins, slot, stats, L, B))
    mass = np.asarray(_histogram_segment(bins, slot, jnp.abs(stats), L, B))
    assert np.max(np.abs(got - segment) / np.maximum(mass, 1e-30)) < 2.0 ** -20


def test_a_description_is_for_the_f32_matmul_operand_alone():
    """Every other impl and both lower-precision modes take a
    description and ignore it; one that does not fit the operand is
    refused where it is traced."""
    rng = np.random.default_rng(5)
    n, F, L, B = 600, 3, 4, 16
    bins = jnp.asarray(rng.integers(0, B, (n, F)), jnp.uint8)
    slot = jnp.asarray(rng.integers(0, L + 1, (n,)), jnp.int32)
    w = (rng.random(n) < 0.8).astype(np.float32)
    stats = jnp.asarray(
        np.stack([rng.normal(size=n).astype(np.float32) * w, w, w], axis=1)
    )

    def run(impl, quant, columns):
        return np.asarray(histogram(
            bins, slot, stats, num_slots=L, num_bins=B, impl=impl,
            quant=quant, stat_columns=columns,
        ))

    for impl, quant in [("segment", "f32"), ("pallas_interpret", "f32"),
                        ("matmul", "bf16x2"), ("matmul", "int8")]:
        assert run(impl, quant, _FOUR).tobytes() == run(
            impl, quant, None).tobytes(), (impl, quant)
    for bad in (
        _SEVEN[:2],  # two columns described of three
        (StatColumn(), StatColumn(same_as=1), StatColumn()),  # itself
        (StatColumn(same_as=1), StatColumn(same_as=2), StatColumn()),
        (StatColumn(pieces=4), StatColumn(), StatColumn()),
    ):
        with pytest.raises(ValueError, match="column"):
            run("matmul", "f32", bad)


def test_split_bf16_takes_a_count_a_column():
    """One count a column: block p holds piece p of the columns with
    more than p pieces, and each piece is the uniform split's."""
    rng = np.random.default_rng(3)
    x = _mixed_magnitudes(rng, (500, 3), -3.0, 3.0)
    x[:, 2] = rng.integers(0, 2, 500)
    whole = np.asarray(split_bf16(jnp.asarray(x), 3).astype(jnp.float32))
    got = np.asarray(split_bf16(jnp.asarray(x), (3, 2, 1)).astype(jnp.float32))
    assert got.shape == (500, 6)
    np.testing.assert_array_equal(got[:, :3], whole[:, :3])  # piece 0
    np.testing.assert_array_equal(got[:, 3:5], whole[:, 3:5])  # piece 1
    np.testing.assert_array_equal(got[:, 5], whole[:, 6])  # piece 2
    assert not whole[:, 5].any() and not whole[:, 8].any()  # 0/1: one piece


def test_segment_chunked_scan_path():
    """The fused-scatter segment impl accumulates identically when the
    example axis is split into scan chunks (memory-bounding path)."""
    import numpy as np

    rng = np.random.default_rng(9)
    bins = jnp.asarray(rng.integers(0, 16, (1000, 4)), jnp.uint8)
    slot = jnp.asarray(rng.integers(0, 9, (1000,)), jnp.int32)
    stats = jnp.asarray(rng.normal(size=(1000, 3)), jnp.float32)
    h1 = histogram(bins, slot, stats, num_slots=8, num_bins=16,
                   impl="segment")  # single-chunk (n < budget)
    h2 = histogram(bins, slot, stats, num_slots=8, num_bins=16,
                   impl="segment", chunk=300)  # 4 scan chunks, padded tail
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-5, atol=1e-5)
