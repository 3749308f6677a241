"""Compiled (non-interpret) Pallas kernel equivalence checks for a REAL TPU
backend (the main suite pins the CPU backend in conftest; Mosaic-specific
miscompiles only show up compiled).

Each ``check_*`` function raises on a mismatch. Two callers:
tests/test_tpu_kernels.py runs this file as a child process (exit codes:
0 = pass, 3 = no TPU available), and chip_smoke.py imports it and calls
``run_all()`` in-process — a child cannot have the chip while the smoke
holds it.

The checks from ``check_front`` down run at the HIGGS width the product
trains at (F=28, B=64, L=255)."""
import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))   # _trees

from _trees import level_order_children  # noqa: E402
from lightgbm_tpu.ops import histogram as H  # noqa: E402
from lightgbm_tpu.ops import pallas_hist as PH  # noqa: E402

# HIGGS width; the row count is not a multiple of any kernel chunk, so every
# check also exercises the padded tail
F, B, L = 28, 64, 255
N = 50_000


def _route_tables(rng, l, f, b, s, categorical=False):
    """Random per-leaf split tables; ~1/8 of the leaves do not split."""
    feat = rng.randint(0, f, size=l).astype(np.int32)
    feat[rng.rand(l) < 0.125] = -1
    cat = {}
    if categorical:
        cat = {"is_cat": jnp.asarray((rng.rand(l) < 0.5).astype(np.int32)),
               "member": jnp.asarray((rng.rand(l, b) < 0.5)
                                     .astype(np.float32))}
    return H.RouteTables(
        feat=jnp.asarray(feat),
        thr=jnp.asarray(rng.randint(0, b, size=l).astype(np.int32)),
        dleft=jnp.asarray(rng.randint(0, 2, size=l).astype(np.int32)),
        new_leaf=jnp.asarray(rng.permutation(l).astype(np.int32)),
        slot_left=jnp.asarray(rng.randint(0, s + 1, size=l).astype(np.int32)),
        slot_right=jnp.asarray(rng.randint(0, s + 1, size=l).astype(np.int32)),
        **cat)


def check_hist_pallas():
    """bf16 hi/lo slot-routed histogram vs the scatter reference."""
    rng = np.random.RandomState(0)
    n, f, b, s = 20000, 12, 64, 6
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = rng.rand(n).astype(np.float32)
    c = np.ones(n, np.float32)
    slot = rng.randint(0, s + 2, size=n).astype(np.int32)
    keep = slot < s
    ref = np.asarray(H.hist_per_leaf_scatter(
        jnp.asarray(bins), jnp.asarray(g * keep), jnp.asarray(h * keep),
        jnp.asarray(c * keep), jnp.asarray(np.where(keep, slot, s)), s, b))
    out = np.asarray(PH.hist_pallas(
        jnp.asarray(bins.T.copy()), jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(c), jnp.asarray(slot), s, b))
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-2)


def check_hist_pallas_q8():
    """int8 histogram: exact integer accumulation, and the 2-channel
    constant-hessian form against the 3-channel one."""
    rng = np.random.RandomState(1)
    n, f, b, s = 20000, 12, 64, 6
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    bins_T = jnp.asarray(bins.T.copy())
    slot = rng.randint(0, s + 2, size=n).astype(np.int32)
    keep = slot < s
    gq = rng.randint(-127, 128, size=n).astype(np.int8)
    hq = rng.randint(0, 128, size=n).astype(np.int8)
    cq = np.ones(n, np.int8)
    # scale 127.0 makes the dequantization factor exactly 1.0, so the output
    # must equal the raw integer sums bit-for-bit (count channel exact)
    outq = np.asarray(PH.hist_pallas_q8(
        bins_T, jnp.asarray(gq), jnp.asarray(hq), jnp.asarray(cq),
        jnp.asarray(slot), s, b, jnp.float32(127.0), jnp.float32(127.0)))
    refq = np.zeros((s, 3, f, b), np.float64)
    for j in range(f):
        for ch, w in enumerate((gq, hq, np.ones(n))):
            np.add.at(refq[:, ch, j, :], (np.where(keep, slot, 0), bins[:, j]),
                      np.where(keep, w, 0))
    np.testing.assert_allclose(outq, refq, rtol=0, atol=0.5)

    # constant-hessian elision: the 2-channel kernel must equal the 3-channel
    # kernel run with hq = cq (the exact quantization of a constant hessian;
    # GrowParams.const_hess docstring)
    h_const = 0.37
    args = (bins_T, jnp.asarray(gq), jnp.asarray(cq), jnp.asarray(cq),
            jnp.asarray(slot), s, b, jnp.float32(127.0),
            jnp.float32(127.0 * h_const))
    out3 = np.asarray(PH.hist_pallas_q8(*args))
    out2 = np.asarray(PH.hist_pallas_q8(*args, const_hess=True))
    np.testing.assert_allclose(out2, out3, rtol=1e-6, atol=1e-4)


def check_route_level():
    """Standalone route kernel vs the XLA gather route: a small level, and
    one whose leaf, feature and slot ids need the decode's high byte
    (600 leaves, 300 columns, 290 slots), with categorical tables."""
    rng = np.random.RandomState(2)
    for l, s, f, b, categorical in ((8, 4, 5, 16, False),
                                    (600, 290, 300, 64, True)):
        n = 30000
        bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
        leaf_id = jnp.asarray(rng.randint(0, l, size=n).astype(np.int32))
        na_bin = jnp.asarray(np.where(np.arange(f) % 3 == 0, 3, 256)
                             .astype(np.int32))
        tables = _route_tables(rng, l, f, b, s, categorical)
        ref_slot, ref_lid = H.route_level(jnp.asarray(bins), leaf_id, tables,
                                          na_bin, s)
        out_slot, out_lid = PH.route_level_pallas(
            jnp.asarray(bins.T.copy()), leaf_id, tables, na_bin, s)
        np.testing.assert_array_equal(np.asarray(ref_lid), np.asarray(out_lid))
        np.testing.assert_array_equal(np.minimum(np.asarray(ref_slot), s),
                                      np.minimum(np.asarray(out_slot), s))


def check_take_small():
    rng = np.random.RandomState(3)
    table = rng.randn(L).astype(np.float32)
    idx = rng.randint(0, L, size=100000).astype(np.int32)
    out = np.asarray(PH.take_small_pallas(jnp.asarray(table),
                                          jnp.asarray(idx)))
    np.testing.assert_allclose(out, table[idx], rtol=1e-6)


def _leaf_sums_ref(g, h, c, lid):
    return np.stack([np.bincount(lid, weights=w.astype(np.float64),
                                 minlength=L) for w in (g, h, c)])


def check_leaf_sums():
    rng = np.random.RandomState(4)
    g = rng.randn(N).astype(np.float32)
    h = rng.rand(N).astype(np.float32)
    c = np.ones(N, np.float32)
    lid = rng.randint(0, L, size=N).astype(np.int32)
    sums = np.asarray(PH.leaf_sums_pallas(
        jnp.asarray(g), jnp.asarray(h), jnp.asarray(c), jnp.asarray(lid), L))
    np.testing.assert_allclose(sums, _leaf_sums_ref(g, h, c, lid),
                               rtol=1e-3, atol=1e-2)


# the two objective families the fused front serves: (spec, const_hess)
_FRONT_SPECS = ((("logloss", 1.0, 1.0, 1.0), False), (("l2",), True))


def _front_inputs(rng, spec):
    bins = rng.randint(0, B - 1, size=(N, F)).astype(np.uint8)
    score = rng.randn(N).astype(np.float32)
    aux = ((rng.rand(N) < 0.5).astype(np.float32) if spec[0] == "logloss"
           else rng.randn(N).astype(np.float32))
    bag = (rng.rand(N) < 0.8).astype(np.float32)
    return bins, score, aux, bag


def check_front():
    """Fused gradient + quantise + root histogram vs the unfused chain
    (XLA gradients -> make_quant -> compiled q8 root pass).

    L2 has no transcendental, so every output must match bit for bit.
    Logloss goes through exp, which compiled Mosaic and XLA may round
    differently in the last ulp: gq/hq may then differ by one quantum on
    rows that sit on a rounding boundary, and hist0 by at most one quantum
    per such row."""
    for spec, const_hess in _FRONT_SPECS:
        rng = np.random.RandomState(5)
        bins, score, aux, bag = (jnp.asarray(a)
                                 for a in _front_inputs(rng, spec))
        bins_T = bins.T
        seed = jnp.int32(7)
        fq, fhist = jax.jit(
            lambda b_, bt, s_, a_, g_: H.grad_quant_hist0(
                b_, s_, a_, g_, seed, spec, B, const_hess=const_hess,
                impl="pallas", bins_T=bt))(bins, bins_T, score, aux, bag)

        def chain(b_, bt, s_, a_, g_):
            grad, hess = PH._grad_rows(spec, s_, a_)
            g, h = grad * g_, hess * g_
            c = (g_ > 0).astype(jnp.float32)
            q = H.make_quant(g, h, c, seed, const_hess=const_hess)
            return q, H.hist_leaf(b_, g, h, c, B, impl="pallas", bins_T=bt,
                                  quant=q)
        rq, rhist = jax.jit(chain)(bins, bins_T, score, aux, bag)

        np.testing.assert_array_equal(np.asarray(fq.cq), np.asarray(rq.cq))
        assert (fq.hq is None) == const_hess
        fhist, rhist = np.asarray(fhist), np.asarray(rhist)
        assert fhist.shape == (3, F, B) and np.isfinite(fhist).all()
        if spec[0] == "l2":
            np.testing.assert_array_equal(np.asarray(fq.gq),
                                          np.asarray(rq.gq))
            for a, b in ((fq.scale_g, rq.scale_g), (fq.scale_h, rq.scale_h)):
                assert float(a) == float(b), (float(a), float(b))
            np.testing.assert_array_equal(fhist, rhist)
            continue
        np.testing.assert_array_equal(fhist[2], rhist[2])   # counts: exact
        for ch, (fa, ra, fs, rs) in enumerate((
                (fq.gq, rq.gq, fq.scale_g, rq.scale_g),
                (fq.hq, rq.hq, fq.scale_h, rq.scale_h))):
            np.testing.assert_allclose(float(fs), float(rs), rtol=1e-6)
            d = np.abs(np.asarray(fa).astype(np.int32)
                       - np.asarray(ra).astype(np.int32))
            assert d.max() <= 1, f"channel {ch}: {d.max()} quanta apart"
            moved = int((d > 0).sum())
            assert moved <= N // 100, f"channel {ch}: {moved} rows moved"
            quantum = float(rs) / 127.0
            np.testing.assert_allclose(fhist[ch], rhist[ch], rtol=1e-5,
                                       atol=(moved + 1) * quantum)


def check_leaf_sums_grad():
    """Leaf renewal with in-register gradients vs leaf_sums_pallas on the
    materialized rows (bit-identical for L2; exp's last ulp for logloss)
    and vs f64 host sums."""
    for spec, _ in _FRONT_SPECS:
        rng = np.random.RandomState(6)
        _, score, aux, bag = _front_inputs(rng, spec)
        lid = rng.randint(0, L, size=N).astype(np.int32)
        out = np.asarray(PH.leaf_sums_grad_pallas(
            jnp.asarray(score), jnp.asarray(aux), jnp.asarray(bag),
            jnp.asarray(lid), spec, L))
        grad, hess = PH._grad_rows(spec, jnp.asarray(score), jnp.asarray(aux))
        g, h = np.asarray(grad) * bag, np.asarray(hess) * bag
        c = (bag > 0).astype(np.float32)
        np.testing.assert_allclose(out, _leaf_sums_ref(g, h, c, lid),
                                   rtol=1e-3, atol=1e-2)
        mat = np.asarray(PH.leaf_sums_pallas(
            jnp.asarray(g), jnp.asarray(h), jnp.asarray(c), jnp.asarray(lid),
            L))
        if spec[0] == "l2":
            np.testing.assert_array_equal(out, mat)
        else:
            np.testing.assert_allclose(out, mat, rtol=1e-4, atol=1e-3)


def check_fused_level():
    """Fused route + int8 histogram level pass at the slot widths the
    255-leaf grower runs (32 and the 127 cap) and the 128 master width,
    with 3 and 2 (constant-hessian) channels, vs hist_routed_scatter."""
    rng = np.random.RandomState(8)
    bins = rng.randint(0, B - 1, size=(N, F)).astype(np.uint8)
    bins_d, bins_T = jnp.asarray(bins), jnp.asarray(bins.T.copy())
    gq = rng.randint(-127, 128, size=N).astype(np.int8)
    hq = rng.randint(0, 128, size=N).astype(np.int8)
    cq = (rng.rand(N) < 0.8).astype(np.int8)
    # a few features carry a missing bin so the default-direction branch runs
    na = np.full(F, 256, np.int32)
    na[::5] = B - 2
    na_bin = jnp.asarray(na)
    h_const = 0.5   # keeps the reference's f32 hessian sums exact
    # live: the leaves that hold rows. The S = 32 group's tables are cut to
    # them (32 of 255, the decode the grower hands a shallow level), once
    # with categorical splits
    for s, live, categorical in ((32, L, False), (32, 32, False),
                                 (32, 32, True), (127, L, True),
                                 (127, L, False), (128, L, False)):
        tables = _route_tables(rng, L, F, B, s, categorical)
        lid = jnp.asarray(rng.randint(0, live, size=N).astype(np.int32))
        for const_hess in (False, True):
            hrow = cq if const_hess else hq
            scale_h = 127.0 * h_const if const_hess else 127.0
            hist, lid2 = PH.hist_routed_fused_q8(
                bins_T, jnp.asarray(gq), jnp.asarray(hrow), jnp.asarray(cq),
                lid, jax.tree.map(lambda a: a[:live], tables), na_bin, s, B,
                jnp.float32(127.0), jnp.float32(scale_h),
                const_hess=const_hess)
            # scale 127 -> dequantization factor exactly 1: the reference
            # accumulates the same integers in f32 (|sums| < 2^24, exact)
            h_ref = (cq * np.float32(h_const) if const_hess
                     else hq.astype(np.float32))
            rhist, rlid = H.hist_routed_scatter(
                bins_d, jnp.asarray(gq.astype(np.float32)),
                jnp.asarray(h_ref), jnp.asarray(cq.astype(np.float32)),
                lid, tables, na_bin, s, B)
            np.testing.assert_array_equal(np.asarray(lid2), np.asarray(rlid))
            np.testing.assert_allclose(np.asarray(hist), np.asarray(rhist),
                                       rtol=0, atol=0.25,
                                       err_msg=f"S={s} live={live} "
                                       f"const_hess={const_hess}")


def _level_order_tree(rng, num_leaves, f, b):
    """Flat arrays of a tree grown level by level, thresholds in the middle
    half of the bins so that rows spread over the leaves. 255 leaves: depth
    8, as HIGGS's trees."""
    m = num_leaves - 1
    lc, rc = level_order_children(num_leaves)
    return tuple(jnp.asarray(a) for a in (
        rng.randint(0, f, size=m).astype(np.int32),
        rng.randint(b // 4, 3 * b // 4, size=m).astype(np.int32),
        rng.rand(m) < 0.5, np.asarray(lc, np.int32), np.asarray(rc, np.int32),
        np.int32(num_leaves)))


def _ms_a_call(fn, reps=10):
    """Host clock around ``reps`` calls queued back to back and one wait:
    the device's time a call once the queue hides the dispatches."""
    import time
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    outs = [fn() for _ in range(reps)]
    jax.block_until_ready(outs)
    return (time.perf_counter() - t0) / reps * 1e3


def check_walk_tree(n=500_000):
    """The whole-tree walk kernel against the XLA walk at the validation
    set's size (500 k x 28, 255 leaves, depth 8; missing bins on every third
    feature): leaves and step count to the bit, and both walks' time."""
    from lightgbm_tpu.ops import predict as P
    rng = np.random.RandomState(8)
    tree = _level_order_tree(rng, L, F, B)
    bins = jnp.asarray(rng.randint(0, B, size=(n, F)).astype(np.uint8))
    na_bin = jnp.asarray(np.where(np.arange(F) % 3 == 0, B - 1, 256)
                         .astype(np.int32))
    bins_T = PH.resident_bins_T(bins, PH.resident_shape(n, F, B))
    assert P.walk_path(bins_T, tree[0]) == "kernel"
    ref_steps, out_steps = [], []
    ref = P.route_bins(*tree, bins, na_bin, L - 1, steps_out=ref_steps)
    out = P.route_bins(*tree, bins, na_bin, L - 1, steps_out=out_steps,
                       bins_T=bins_T)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert int(ref_steps[0]) == int(out_steps[0]) == 8
    assert len(np.unique(np.asarray(out))) > L // 2   # rows spread wide
    xla = _ms_a_call(lambda: P.route_bins(*tree, bins, na_bin, L - 1))
    kern = _ms_a_call(lambda: P.route_bins(*tree, bins, na_bin, L - 1,
                                           bins_T=bins_T))
    print(f"walk_tree n={n} f={F} leaves={L} steps=8: kernel {kern:.3f} ms "
          f"a call, XLA walk {xla:.3f} ms a call", flush=True)


CHECKS = (check_hist_pallas, check_hist_pallas_q8, check_route_level,
          check_take_small, check_leaf_sums, check_front,
          check_leaf_sums_grad, check_fused_level, check_walk_tree)


def run_all(names=()):
    """Run every check (or those named) on the current (TPU) backend;
    returns the names."""
    checks = [fn for fn in CHECKS if not names or fn.__name__ in names]
    for fn in checks:
        fn()
        print(f"{fn.__name__} OK", flush=True)
    return [fn.__name__ for fn in checks]


def main():
    if jax.default_backend() != "tpu":
        print(f"NO_TPU backend={jax.default_backend()}")
        return 3
    run_all(sys.argv[1:])
    print("TPU_KERNELS_OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
