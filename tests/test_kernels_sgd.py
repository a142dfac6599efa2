"""SGD kernels (``sgd_block`` and its wide form ``sgd_block_wide``): K
models against the vmapped oracle across shapes/kinds, exact update
counts, the rule that picks the form, and convergence props."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels.sgd.ops import sgd_train
from repro.kernels.sgd.ref import loss_ref, sgd_ref
from repro.kernels.sgd import sgd
from repro.kernels.sgd.sgd import sgd_block, sgd_block_wide

LRS = [0.05, 0.2, 0.02, 0.001]
L2S = [1e-4, 0.0, 0.01, 0.1]


def _kernel(data, lr, l2, x0, form=sgd_block, **kw):
    """A kernel with lr/l2 as traced operands, in interpret mode; the
    wide form's data padded with zero rows to whole lane groups."""
    if form is sgd_block_wide:
        n1 = data.shape[0]
        data = jnp.pad(data, ((0, sgd.wide_rows(n1 - 1) - n1), (0, 0)))
    return jax.jit(lambda d, lr, l2, x: form(
        d, lr, l2, x, interpret=True, **kw))(data, lr, l2, x0)


def _feature_major(a, b):
    return jnp.concatenate([a.T, b[None]], axis=0)


def _blocks_of_256(form, n):
    """Either form's keyword for data blocks of 256 rows."""
    if form is sgd_block:
        return {"block_rows": 256}
    return {"block_bytes": 256 * 4 * sgd.wide_rows(n)}


def _case(form, *shape, tag=""):
    return pytest.param(*shape, form, id=tag + "-".join(map(str, shape)))


@pytest.mark.parametrize("m,n,mb,k,epochs,form", [
    # the single-model kernel's shapes, which this kernel replaced
    _case(sgd_block, 128, 64, 8, 1, 3), _case(sgd_block, 256, 128, 16, 1, 3),
    _case(sgd_block, 512, 256, 32, 1, 3),
    # the trainer's minibatch at HIGGS's width, in blocks of 256 rows:
    # whole blocks, a ragged last block, one block padded only to 16
    _case(sgd_block, 512, 28, 16, 1, 1), _case(sgd_block, 560, 28, 16, 1, 1),
    _case(sgd_block, 208, 28, 16, 1, 1), _case(sgd_block, 512, 28, 16, 4, 1),
    _case(sgd_block, 560, 28, 16, 4, 1), _case(sgd_block, 208, 28, 16, 4, 1),
    # the wide form at two widths, the same three kinds of last block
    *(_case(sgd_block_wide, m, n, 16, k, 1, tag="wide-")
      for n in (300, 1000)
      for m, k in ((512, 4), (560, 1), (208, 4), (560, 4), (208, 1))),
    _case(sgd_block_wide, 560, 300, 16, 4, 2, tag="wide-"),
])
@pytest.mark.parametrize("kind", ["ridge", "logreg"])
def test_sgd_block_matches_vmapped_ref(rng, m, n, mb, k, epochs, form,
                                       kind):
    """K models side by side against ``jax.vmap(sgd_ref)``: the same
    updates in the same order, summed in another order (float32 VPU sums
    in the kernel), hence a relative tolerance and not bit equality."""
    a = jnp.asarray(rng.uniform(-1, 1, size=(m, n)), jnp.float32)
    b = jnp.asarray(rng.uniform(0, 1, size=m), jnp.float32)
    x0 = jnp.asarray(rng.normal(size=(k, n)) * 0.1, jnp.float32)
    lr = jnp.asarray(LRS[:k], jnp.float32)
    l2 = jnp.asarray(L2S[:k], jnp.float32)
    if form is sgd_block_wide:
        lr = lr * 28 / n             # rows of squared norm about n / 3
    want = jax.vmap(lambda x, lr, l2: sgd_ref(
        a, b, x, lr=lr, l2=l2, minibatch=mb, epochs=epochs, kind=kind))(
        x0, lr, l2)
    got = _kernel(_feature_major(a, b), lr, l2, x0, form=form, minibatch=mb,
                  epochs=epochs, kind=kind, **_blocks_of_256(form, n))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * float(
                                   jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("m,n,form", [
    pytest.param(208, 28, sgd_block, id="208"),
    pytest.param(560, 28, sgd_block, id="560"),
    pytest.param(208, 300, sgd_block_wide, id="wide-208"),
    pytest.param(560, 300, sgd_block_wide, id="wide-560"),
])
def test_sgd_block_applies_no_pad_update(m, n, form):
    """On all-zero rows a ridge update only shrinks: x <- x (1 - 2 lr l2).
    After m // 16 minibatches the weights carry exactly that many
    factors, not one more for each 16 pad rows of the last 256-row
    block."""
    k = 4
    x0 = jnp.ones((k, n), jnp.float32)
    lr = jnp.asarray(LRS, jnp.float32)
    l2 = jnp.asarray(L2S, jnp.float32)
    got = _kernel(jnp.zeros((n + 1, m), jnp.float32), lr, l2, x0, form=form,
                  minibatch=16, kind="ridge", **_blocks_of_256(form, n))
    shrink = np.asarray(1 - 2 * lr * l2, np.float64)[:, None]
    np.testing.assert_allclose(np.asarray(got), np.broadcast_to(
        shrink ** (m // 16), (k, n)), rtol=1e-5)
    assert not np.allclose(np.asarray(got)[3], shrink[3] ** (m // 16 + 1),
                           rtol=1e-5)


def test_sgd_form_is_chosen_by_feature_count():
    """One rule picks the trainer's form from the feature count: HIGGS's
    28 features keep ``sgd_block``; epsilon's 2,000 take the wide form,
    whose staged rows are the features and label padded to 128 lanes."""
    assert not sgd.wide(28)
    assert not sgd.wide(sgd.WIDE_FROM_ROWS - 2)
    assert sgd.wide(sgd.WIDE_FROM_ROWS - 1)
    assert sgd.wide(300) and sgd.wide(2000)
    assert sgd.wide_rows(2000) == 2048 and sgd.wide_rows(127) == 128
    assert sgd.wide_rows(300) == 384


@pytest.mark.parametrize("kind", ["ridge", "logreg"])
def test_sgd_train_pallas_is_the_single_model_kernel(rng, kind):
    """``sgd_train(impl="pallas")`` is the kernel's K=1 case on the
    feature-major layout."""
    a = jnp.asarray(rng.uniform(-1, 1, size=(256, 28)), jnp.float32)
    b = jnp.asarray(rng.uniform(0, 1, size=256), jnp.float32)
    x0 = jnp.zeros(28, jnp.float32)
    got = sgd_train(a, b, x0, lr=0.05, l2=1e-4, epochs=2, kind=kind,
                    impl="pallas", interpret=True)
    want = _kernel(_feature_major(a, b), jnp.asarray([0.05]),
                   jnp.asarray([1e-4]), x0[None], epochs=2, kind=kind)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want)[0])


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), epochs=st.integers(1, 5))
def test_more_epochs_do_not_increase_train_loss_much(seed, epochs):
    """Property: loss after N+1 epochs <= loss after N (tiny slack for SGD
    noise) on a well-conditioned ridge problem."""
    r = np.random.default_rng(seed)
    m, n = 256, 64
    w = r.normal(size=n)
    a = jnp.asarray(r.uniform(-1, 1, size=(m, n)), jnp.float32)
    b = jnp.asarray(np.asarray(a) @ w, jnp.float32)
    x0 = jnp.zeros(n, jnp.float32)
    l1 = float(loss_ref(a, b, sgd_ref(a, b, x0, lr=0.02, minibatch=16,
                                      epochs=epochs), kind="ridge"))
    l2 = float(loss_ref(a, b, sgd_ref(a, b, x0, lr=0.02, minibatch=16,
                                      epochs=epochs + 1), kind="ridge"))
    assert l2 <= l1 * 1.05


def test_minibatch_size_convergence_fig11(rng):
    """Paper Fig. 11: B=16 converges to (approximately) the same loss as
    B=1 on the same budget."""
    m, n = 512, 128
    w = rng.normal(size=n)
    a = jnp.asarray(rng.uniform(-1, 1, size=(m, n)), jnp.float32)
    b = jnp.asarray((np.asarray(a) @ w > 0).astype(np.float32))
    x0 = jnp.zeros(n, jnp.float32)
    # linear lr scaling across minibatch sizes (mean-gradient semantics)
    l_b1 = float(loss_ref(a, b, sgd_ref(a, b, x0, lr=0.03, minibatch=1,
                                        epochs=8, kind="logreg"),
                          kind="logreg"))
    l_b16 = float(loss_ref(a, b, sgd_ref(a, b, x0, lr=0.03 * 16, minibatch=16,
                                         epochs=8, kind="logreg"),
                           kind="logreg"))
    assert abs(l_b1 - l_b16) < 0.1
    assert l_b16 < 0.6                     # actually learned something
