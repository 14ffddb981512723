"""Property-based differential kernel suite.

Every registered SpMV execution path — csr, ell, csr-seg, hyb under all
semirings, plus dia and bell under plus-times — is pinned against a
dense reference on randomized matrices drawn from the structure families
the paper measures (FD stencils, R-MAT power laws) plus the degenerate
shapes that have historically broken padded layouts: empty rows, nnz=0,
a single dense row, duplicate-structure rows.

Bit-exactness strategy: data and x are small *integer-valued* float32,
so every summation order is exact in float32 and plus-times results must
be BIT-IDENTICAL across every kernel and the dense reference — not
merely allclose.  The non-plus-times semirings (min/max reductions and
integer adds) are exact too; their comparisons only relax to allclose to
let matching ±inf identities compare equal.

Property tests are driven by `hypothesis` when installed (CI installs
requirements-dev.txt; the `kernel-properties` profile in conftest.py
sets the example budget and `--hypothesis-seed` pins the search).
Without it they skip and the named regression tests below still run.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from _opt_deps import HAVE_HYPOTHESIS, given, settings, st  # noqa: F401

from repro import plan
from repro.core.formats import CSR, ELL, HYB
from repro.core.generators import fd_matrix, rmat_matrix
from repro.graph.semiring import SEMIRINGS
from repro.kernels import _layout as kl

# Formats the plan compiler can be forced to, by semiring compatibility.
PLUS_TIMES_FORMATS = ("csr", "csr-seg", "ell", "hyb", "dia", "bell")
SEMIRING_FORMATS = ("csr", "csr-seg", "ell", "hyb")
REORDERINGS = ("none", "rcm", "degree-sort")
FAMILIES = ("fd", "rmat", "empty", "empty-rows", "single-dense-row",
            "duplicate-rows")


# ---------------------------------------------------------------------------
# matrix families (structure only; values are drawn separately)
# ---------------------------------------------------------------------------

def _structure(family: str, n: int, seed: int):
    """(rows, cols, n_rows, n_cols) nonzero pattern for one family."""
    rng = np.random.default_rng(seed)
    if family == "fd":
        m = fd_matrix(max(n, 16), seed=seed)
        rows = np.repeat(np.arange(m.n_rows, dtype=np.int64),
                         np.diff(np.asarray(m.indptr)))
        return rows, np.asarray(m.indices, dtype=np.int64), m.n_rows, m.n_cols
    if family == "rmat":
        n2 = 1 << max(int(np.ceil(np.log2(max(n, 16)))), 4)  # R-MAT: pow2
        m = rmat_matrix(n2, seed=seed)
        rows = np.repeat(np.arange(m.n_rows, dtype=np.int64),
                         np.diff(np.asarray(m.indptr)))
        return rows, np.asarray(m.indices, dtype=np.int64), m.n_rows, m.n_cols
    if family == "empty":
        z = np.empty(0, dtype=np.int64)
        return z, z, n, n
    if family == "empty-rows":
        # only even rows populated: every odd row (and any unlucky even
        # one) exercises the empty-row identity path
        nnz = max(1, 2 * n)
        rows = rng.integers(0, (n + 1) // 2, nnz) * 2
        cols = rng.integers(0, n, nnz)
        return rows.astype(np.int64), cols.astype(np.int64), n, n
    if family == "single-dense-row":
        # one hub row touching every column + a sparse remainder: the
        # heavy/light split and the segment carry both trigger
        hub = int(rng.integers(0, n))
        rows = [np.full(n, hub, dtype=np.int64)]
        cols = [np.arange(n, dtype=np.int64)]
        extra = max(1, n // 2)
        rows.append(rng.integers(0, n, extra).astype(np.int64))
        cols.append(rng.integers(0, n, extra).astype(np.int64))
        return np.concatenate(rows), np.concatenate(cols), n, n
    if family == "duplicate-rows":
        # every row shares one column pattern (degree-sort ties, identical
        # per-segment row windows)
        k = int(rng.integers(1, min(n, 6) + 1))
        pattern = rng.choice(n, size=k, replace=False).astype(np.int64)
        rows = np.repeat(np.arange(n, dtype=np.int64), k)
        cols = np.tile(pattern, n)
        return rows, cols, n, n
    raise ValueError(family)


def _int_csr(family: str, n: int, seed: int, lo: int = -8, hi: int = 8
             ) -> CSR:
    """Family structure + integer-valued float32 data in [lo, hi] \\ {0}
    (zero values at column 0 are indistinguishable from padding by
    design — see `_check_ell_padding_absorbing` — so they are avoided)."""
    rows, cols, n_rows, n_cols = _structure(family, n, seed)
    rng = np.random.default_rng(seed + 1)
    vals = rng.integers(lo, hi + 1, size=rows.shape[0])
    vals[vals == 0] = 1
    return CSR.from_coo(rows, cols, vals.astype(np.float32), n_rows, n_cols)


def _int_x(n: int, seed: int, lo: int = -8, hi: int = 8) -> np.ndarray:
    return np.random.default_rng(seed + 2).integers(
        lo, hi + 1, size=n).astype(np.float32)


def _dense_ref(csr: CSR, x: np.ndarray, sr_name: str = "plus_times"
               ) -> np.ndarray:
    """Entry-by-entry dense oracle in float32 (exact on integer values)."""
    ops = {"plus_times": (np.add, np.multiply, np.float32(0.0)),
           "min_plus": (np.minimum, np.add, np.float32(np.inf)),
           "or_and": (np.maximum, np.multiply, np.float32(0.0)),
           "max_times": (np.maximum, np.multiply, np.float32(0.0))}
    add, mul, ident = ops[sr_name]
    ip = np.asarray(csr.indptr)
    idx = np.asarray(csr.indices)
    d = np.asarray(csr.data, dtype=np.float32)
    y = np.full(csr.n_rows, ident, dtype=np.float32)
    for r in range(csr.n_rows):
        for p in range(int(ip[r]), int(ip[r + 1])):
            y[r] = add(y[r], np.float32(mul(d[p], np.float32(x[idx[p]]))))
    return y


def _execute(csr: CSR, x: np.ndarray, fmt: str, reorder: str = "none",
             semiring: str = "plus_times", seg_len: int = 512) -> np.ndarray:
    p = plan.compile(csr, format=fmt, reorder=reorder, predictor="none",
                     semiring=semiring, seg_len=seg_len)
    return np.asarray(p.execute(jnp.asarray(x), interpret=True))


# ---------------------------------------------------------------------------
# the differential properties
# ---------------------------------------------------------------------------

@given(family=st.sampled_from(FAMILIES), n=st.integers(4, 32),
       seed=st.integers(0, 2 ** 16), reorder=st.sampled_from(REORDERINGS))
def test_plus_times_bit_exact_across_all_formats(family, n, seed, reorder):
    """Every format's plan — reordered or not — returns the bit-identical
    float32 vector the dense reference computes on integer operands."""
    csr = _int_csr(family, n, seed)
    x = _int_x(csr.n_cols, seed)
    ref = _dense_ref(csr, x)
    for fmt in PLUS_TIMES_FORMATS:
        y = _execute(csr, x, fmt, reorder=reorder)
        assert y.dtype == ref.dtype and y.shape == ref.shape
        assert np.array_equal(y, ref), \
            f"{fmt}/{reorder} diverged on {family}(n={n}, seed={seed})"


@given(family=st.sampled_from(FAMILIES), n=st.integers(4, 32),
       seed=st.integers(0, 2 ** 16),
       sr_name=st.sampled_from(("min_plus", "or_and", "max_times")))
def test_semirings_match_dense_on_every_format(family, n, seed, sr_name):
    """min_plus / or_and / max_times agree with the dense oracle on every
    absorbing-pad format (allclose so paired ±inf identities compare)."""
    if sr_name == "or_and":         # boolean embedding: {0,1} indicators
        csr = _int_csr(family, n, seed, lo=1, hi=1)
        x = _int_x(csr.n_cols, seed, lo=0, hi=1)
    elif sr_name == "max_times":    # only a semiring over nonnegatives
        csr = _int_csr(family, n, seed, lo=1, hi=8)
        x = _int_x(csr.n_cols, seed, lo=0, hi=8)
    else:
        csr = _int_csr(family, n, seed)
        x = _int_x(csr.n_cols, seed)
    ref = _dense_ref(csr, x, sr_name)
    for fmt in SEMIRING_FORMATS:
        y = _execute(csr, x, fmt, semiring=sr_name)
        np.testing.assert_allclose(
            y, ref, rtol=1e-6, atol=0,
            err_msg=f"{fmt}/{sr_name} on {family}(n={n}, seed={seed})")


@given(n=st.integers(8, 48), seed=st.integers(0, 2 ** 16),
       seg_len=st.sampled_from((8, 16, 64)))
def test_segment_boundary_carry_is_exact(n, seed, seg_len):
    """A dense hub row split across many short segments must reassemble
    exactly through the carry-out merge (the seg kernel's one hard
    invariant)."""
    csr = _int_csr("single-dense-row", n, seed)
    x = _int_x(csr.n_cols, seed)
    ref = _dense_ref(csr, x)
    y = _execute(csr, x, "csr-seg", seg_len=seg_len)
    assert np.array_equal(y, ref)
    y_hyb = _execute(csr, x, "hyb", seg_len=seg_len)
    assert np.array_equal(y_hyb, ref)


def _random_delta(csr: CSR, n_ins: int, n_del: int, seed: int,
                  lo: int = 1, hi: int = 8):
    """Insert/delete batch against `csr`: inserts at absent coordinates
    with integer-valued f32 weights, deletes at present ones."""
    from repro.core.delta import EdgeDelta

    rng = np.random.default_rng(seed + 3)
    ip = np.asarray(csr.indptr)
    rows = np.repeat(np.arange(csr.n_rows, dtype=np.int64), np.diff(ip))
    cols = np.asarray(csr.indices, dtype=np.int64)
    present = set(zip(rows.tolist(), cols.tolist()))
    inserts, seen = [], set()
    tries = 0
    while len(inserts) < n_ins and tries < 200:
        tries += 1
        r = int(rng.integers(csr.n_rows)) if csr.n_rows else 0
        c = int(rng.integers(csr.n_cols)) if csr.n_cols else 0
        if csr.n_rows and (r, c) not in present and (r, c) not in seen:
            inserts.append((r, c, float(rng.integers(lo, hi + 1))))
            seen.add((r, c))
    deletes = []
    if n_del and rows.size:
        picks = rng.choice(rows.size, size=min(n_del, rows.size),
                           replace=False)
        deletes = [(int(rows[p]), int(cols[p])) for p in picks]
    return EdgeDelta.from_updates(csr, inserts=inserts, deletes=deletes)


@given(family=st.sampled_from(("fd", "rmat")), n=st.integers(8, 32),
       seed=st.integers(0, 2 ** 16),
       sr_name=st.sampled_from(("plus_times", "min_plus", "or_and",
                                "max_times")),
       reorder=st.sampled_from(("none", "rcm")),
       n_ins=st.integers(0, 6), n_del=st.integers(0, 4))
def test_overlaid_plan_matches_recompiled_materialization(
        family, n, seed, sr_name, reorder, n_ins, n_del):
    """An overlaid plan answers exactly like a fresh compile of the
    materialized matrix: bit-identical under plus_times (deletes ride as
    exact negations), allclose under the ⊕-only semirings (insert-only
    — their deletes are overlay-ineligible and must be refused)."""
    from repro.core.delta import EdgeDelta
    from repro.plan import overlay

    if sr_name == "or_and":
        csr = _int_csr(family, n, seed, lo=1, hi=1)
        x = _int_x(csr.n_cols, seed, lo=0, hi=1)
        lo = hi = 1
    elif sr_name == "max_times":
        csr = _int_csr(family, n, seed, lo=1, hi=8)
        x = _int_x(csr.n_cols, seed, lo=0, hi=8)
        lo, hi = 1, 8
    else:
        csr = _int_csr(family, n, seed)
        x = _int_x(csr.n_cols, seed)
        lo, hi = 1, 8
    if sr_name != "plus_times":
        n_del = 0                      # ⊕-only: deletes are ineligible
    delta = _random_delta(csr, n_ins, n_del, seed, lo=lo, hi=hi)

    base = plan.compile(csr, format="csr", reorder=reorder,
                        predictor="none", semiring=sr_name)
    ov = overlay(base, delta, staleness_budget=1.0)
    got = np.asarray(ov.execute(jnp.asarray(x), interpret=True))

    fresh = plan.compile(csr.apply_delta(delta), format="csr",
                         reorder=reorder, predictor="none",
                         semiring=sr_name)
    ref = np.asarray(fresh.execute(jnp.asarray(x), interpret=True))
    if delta.nnz == 0:
        assert ov.fingerprint != base.fingerprint or delta.nnz == 0
        assert np.array_equal(
            got, np.asarray(base.execute(jnp.asarray(x), interpret=True)))
    if sr_name == "plus_times":
        assert np.array_equal(got, ref), \
            f"overlay diverged: {family}(n={n}, seed={seed}) " \
            f"+{delta.n_inserts}/-{delta.n_deletes} {reorder}"
    else:
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=0)

    # ⊕-only deletes cannot be overlaid: the algebra has no inverse
    if sr_name != "plus_times" and rows_nonempty(csr):
        ip = np.asarray(csr.indptr)
        rr = np.repeat(np.arange(csr.n_rows, dtype=np.int64), np.diff(ip))
        cc = np.asarray(csr.indices, dtype=np.int64)
        bad = EdgeDelta.from_updates(
            csr, deletes=[(int(rr[0]), int(cc[0]))])
        from repro.plan.overlay import overlay_eligible
        assert not overlay_eligible(bad, sr_name)


def rows_nonempty(csr: CSR) -> bool:
    return csr.nnz > 0


@given(n=st.integers(4, 48), seed=st.integers(0, 2 ** 16))
def test_permutation_round_trip_identity(n, seed):
    """permute_x then restore_y through any strategy is the identity on
    the multiply: a reordered plan's output is bit-identical to the
    unreordered plan of the same format."""
    csr = _int_csr("rmat", n, seed)
    x = _int_x(csr.n_cols, seed)
    base = _execute(csr, x, "csr", reorder="none")
    for reorder in ("rcm", "degree-sort"):
        assert np.array_equal(_execute(csr, x, "csr", reorder=reorder), base)


# ---------------------------------------------------------------------------
# named regressions (runnable without hypothesis)
# ---------------------------------------------------------------------------

def _empty_csr(n: int = 8) -> CSR:
    z = np.empty(0, dtype=np.int64)
    return CSR.from_coo(z, z, np.empty(0, dtype=np.float32), n, n)


@pytest.mark.parametrize("fmt", PLUS_TIMES_FORMATS)
def test_nnz0_every_forced_format(fmt):
    csr = _empty_csr(8)
    x = _int_x(8, seed=0)
    y = _execute(csr, x, fmt)
    assert np.array_equal(y, np.zeros(8, np.float32))


@pytest.mark.parametrize("sr_name", ["min_plus", "or_and", "max_times"])
@pytest.mark.parametrize("fmt", SEMIRING_FORMATS)
def test_nnz0_semiring_identity(fmt, sr_name):
    """An all-empty matrix reduces every row to the ⊕-identity."""
    csr = _empty_csr(8)
    x = _int_x(8, seed=0, lo=0, hi=1)
    y = _execute(csr, x, fmt, semiring=sr_name)
    ident = SEMIRINGS[sr_name].identity
    assert np.array_equal(y, np.full(8, ident, np.float32))


def test_zero_row_ell_layout():
    """n_rows=0: `prepare_ell` must not produce a zero-length Pallas grid
    (regression: round_up(0, bm) == 0)."""
    csr = CSR(data=jnp.zeros((0,), jnp.float32),
              indices=jnp.zeros((0,), jnp.int32),
              indptr=jnp.zeros((1,), jnp.int32), n_rows=0, n_cols=4)
    ell = ELL.from_csr(csr)
    y = kl.spmv_ell_prepared(kl.prepare_ell(ell), jnp.ones((4,), jnp.float32),
                             interpret=True)
    assert y.shape == (0,)


def test_out_of_range_sources_rejected():
    from repro.graph.drivers import bfs, sssp

    csr = _int_csr("rmat", 16, seed=0, lo=1, hi=4)
    for bad in (-1, csr.n_rows, csr.n_rows + 7):
        with pytest.raises(ValueError, match="out of range"):
            bfs(csr, bad)
        with pytest.raises(ValueError, match="out of range"):
            sssp(csr, bad)


def test_hyb_routes_hub_rows_to_heavy():
    """The dense hub row lands whole in the heavy partition and is
    all-padding in the light slab; light width stays <= threshold."""
    csr = _int_csr("single-dense-row", 32, seed=1)
    hyb = HYB.from_csr(csr)
    lengths = np.diff(np.asarray(csr.indptr))
    hub = int(np.argmax(lengths))
    assert hub in hyb.heavy_row_ids()
    assert hyb.light_width <= hyb.threshold
    assert np.all(np.asarray(hyb.data)[hub] == 0.0)     # all-padding row
    # heavy stream is column-sorted: the hub gathers stream x in order
    assert np.all(np.diff(np.asarray(hyb.hcols)) >= 0)


def test_repeated_compiles_produce_identical_plans():
    """Candidate enumeration is sorted by (format, reordering), so two
    compiles of the same matrix — and the same compile under a different
    dict insertion order — pick the same plan, bit for bit."""
    csr = rmat_matrix(256, seed=2)
    x = jnp.asarray(_int_x(csr.n_cols, seed=0))
    plans = [plan.compile(csr, reorder="auto", predictor="analytic",
                          threads=4) for _ in range(3)]
    first = plans[0]
    for p in plans[1:]:
        assert p.format_name == first.format_name
        assert p.chosen == first.chosen
        assert list(p.predicted) == list(first.predicted)
        assert np.array_equal(np.asarray(p.execute(x, interpret=True)),
                              np.asarray(first.execute(x, interpret=True)))


def test_nnz_trace_slices_tile_the_full_trace():
    """Merge-partition trace slices must tile the global trace exactly —
    including the headers of *leading* empty rows, which sit before the
    first cut's containing row and belong to thread 0 (regression: they
    were dropped from every slice)."""
    from repro.core.cache_model import SANDY_BRIDGE
    from repro.core.partition import nnz_split
    from repro.parallel import nnz_partitioned_traces
    from repro.telemetry.hierarchy import spmv_address_trace

    rows = np.array([5, 5, 6, 6, 7], dtype=np.int64)   # rows 0-4 empty
    cols = np.array([1, 3, 0, 2, 5], dtype=np.int64)
    vals = np.ones(5, dtype=np.float32)
    csr = CSR.from_coo(rows, cols, vals, 8, 8)
    trace = spmv_address_trace(csr, SANDY_BRIDGE)
    for parts in (1, 2, 3, 5):
        slices = nnz_partitioned_traces(csr, nnz_split(csr, parts),
                                        SANDY_BRIDGE)
        assert np.array_equal(np.concatenate(slices), trace)
