"""End-to-end behaviour tests for the paper's system.

The full SparseLUT toolflow (paper Fig. 6), miniaturized for CPU:
  1. connectivity search (Alg. 1 + 2) on a synthetic dataset;
  2. LUT-DNN QAT retraining with the learned mask;
  3. truth-table synthesis;
  4. LUT-mode serving (gather kernel) == QAT model, bit-exact argmax;
plus the paper's two headline claims, at reduced scale:
  * optimized connectivity >= random connectivity accuracy (Table VII);
  * PolyLUT-Add reduces modeled LUT cost at comparable accuracy
    (Tables II/IV via the analytic cost model).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import paper_models as PM
from repro.core import cost_model as CM
from repro.core import lut_synth as LS
from repro.core import lutdnn as LD
from repro.data.loader import batch_iterator, train_test_split
from repro.data.synthetic import make_dataset
from repro.kernels.lut_gather import ops as lg_ops
from repro.parallel import sharding as SH


@pytest.fixture(scope="module")
def jsc():
    return train_test_split(make_dataset("jsc", n_samples=3000, seed=0))


@pytest.fixture(scope="module")
def mnist():
    return train_test_split(make_dataset("mnist", n_samples=3000, seed=0))


def _train(spec, data, steps=150, seed=0, conn=None, lr=5e-3):
    init_state, step = LD.make_train_step(spec, lr=lr)
    state = init_state(jax.random.key(seed))
    if conn is not None:
        state["model"]["conn"] = conn
    jstep = jax.jit(step)
    it = batch_iterator(data["train"], 256, seed=seed)
    for _ in range(steps):
        state, _ = jstep(state, next(it))
    ev = jax.jit(LD.make_eval_step(spec))
    acc, _ = ev(state["model"], data["test"])
    return float(acc), state["model"]


@pytest.mark.slow
def test_full_toolflow_search_train_synthesise_serve(jsc):
    spec = PM.tiny("jsc", degree=1, adder_width=2, fan_in=2)

    # 1. connectivity search (full-precision theta/sign model)
    it = batch_iterator(jsc["train"], 256, seed=1)
    masks, hist, _ = LD.search_connectivity(
        jax.random.key(1), spec, it, n_steps=100, phase_frac=0.6, eps2=2e-3)
    conn = LD.masks_to_conn(masks, spec)

    # 2. QAT retraining with the learned mask
    acc, model = _train(spec, jsc, conn=conn, seed=2)
    assert acc > 0.40            # 5 classes, chance 0.2

    # 3. synthesis to truth tables
    tables = LS.synthesise(model, spec)

    # 4. LUT-mode serving == QAT forward (argmax agreement on test set)
    x = jsc["test"]["x"][:256]
    fq = spec.layer_specs()[0].in_quant
    codes = fq.to_code(fq.clip(jnp.asarray(x)))
    out_codes = lg_ops.lut_network(tables, codes)
    lut_pred = np.asarray(jnp.argmax(LS.OUTPUT_QUANT.from_code(out_codes), -1))
    logits, _ = LD.forward(model, spec, jnp.asarray(x), train=False)
    qat_np = np.asarray(logits)
    qat_pred = qat_np.argmax(-1)
    # deployment contract: any disagreement must be a sub-step tie —
    # the QAT logit at the LUT's pick within one 16-bit OUTPUT_QUANT
    # grid step of the QAT max (two logits that close quantize to the
    # SAME code, so the LUT path cannot order them)
    agree = lut_pred == qat_pred
    tie = qat_np[np.arange(len(qat_np)), lut_pred] >= \
        qat_np.max(-1) - (LS.OUTPUT_QUANT.step + 1e-6)
    assert (agree | tie).all()
    assert agree.mean() > 0.95


def test_paper_claim_optimized_connectivity_beats_random(jsc):
    """Table VII, reduced: SparseLUT mask >= mean(random masks).

    QAT retraining at this scale has high seed variance (single runs
    span ~0.34-0.57), so BOTH arms are averaged over the same retrain
    seeds; fan_in=3 matches the other tiny-config tests (the harder
    fan_in=2 configuration has its own claim test below).
    """
    spec = PM.tiny("jsc", degree=1, fan_in=3)
    seeds = (10, 11, 12)

    rand_accs = [_train(spec, jsc, seed=s)[0] for s in seeds]

    it = batch_iterator(jsc["train"], 256, seed=3)
    masks, _, _ = LD.search_connectivity(
        jax.random.key(3), spec, it, n_steps=150, phase_frac=0.6, eps2=2e-3)
    conn = LD.masks_to_conn(masks, spec)
    opt_accs = [_train(spec, jsc, conn=conn, seed=s)[0] for s in seeds]

    # never meaningfully worse
    assert np.mean(opt_accs) >= np.mean(rand_accs) - 0.01


def test_connectivity_search_fan_in2_anomaly(jsc):
    """The (former) fan_in=2 anomaly, now a positive claim test — the
    same protocol as the fan_in=3 claim test above (seed-averaged
    retrain arms, identical search budget), only the fan-in differs.

    This was a strict-xfail characterization test while the ROADMAP
    anomaly was open: the greedy phase-boundary truncation plus a
    float-relu search proxy made fan_in=2 searched masks retrain WORSE
    than random (~0.46 vs ~0.55 on tiny-jsc).  The non-greedy ramped
    schedule with scored regrowth and the quantization-matched search
    proxy flipped it (searched ~0.65 on the same protocol — see the
    sparse_train / search_forward module docs for the post-mortem)."""
    spec = PM.tiny("jsc", degree=1, fan_in=2)
    seeds = (10, 11, 12)

    rand_accs = [_train(spec, jsc, seed=s)[0] for s in seeds]

    it = batch_iterator(jsc["train"], 256, seed=3)
    masks, _, _ = LD.search_connectivity(
        jax.random.key(3), spec, it, n_steps=150, phase_frac=0.6, eps2=2e-3)
    conn = LD.masks_to_conn(masks, spec)
    opt_accs = [_train(spec, jsc, conn=conn, seed=s)[0] for s in seeds]

    assert np.mean(opt_accs) >= np.mean(rand_accs) - 0.01


def test_population_search_sharded_bit_identical(jsc):
    """Fast lane: the population search's seed axis is embarrassingly
    parallel, so sharding it over ``serving_mesh(2)`` must be
    BIT-IDENTICAL to the single-device run — masks AND selection
    scores.  Also pins the history contract: integer cadence entries
    plus the final step, population-aggregated."""
    spec = PM.tiny("jsc", degree=1, fan_in=2)
    kw = dict(n_steps=24, n_seeds=4, phase_frac=0.6, eps2=2e-3)

    it = batch_iterator(jsc["train"], 128, seed=5)
    masks_s, scores_s, hist, _ = LD.search_connectivity_population(
        jax.random.key(5), spec, it, mesh=SH.serving_mesh(2), **kw)
    it = batch_iterator(jsc["train"], 128, seed=5)
    masks_1, scores_1, _, _ = LD.search_connectivity_population(
        jax.random.key(5), spec, it, mesh=None, **kw)

    for a, b in zip(masks_s, masks_1):
        assert a.shape[0] == 4                      # (n_seeds, n_in, n_out)
        assert jnp.array_equal(a, b)
    assert jnp.array_equal(scores_s, scores_1)

    # extracted masks honor fan-in exactly; best-of-population selects
    # the argmax score (ties -> lowest seed)
    for m, ls in zip(masks_s, spec.layer_specs()):
        assert (np.asarray(m.sum(1)) == ls.total_fan_in).all()
    best_masks, best = LD.select_best_masks(masks_s, scores_s)
    assert best == int(jnp.argmax(scores_s))
    assert all(jnp.array_equal(bm, m[best])
               for bm, m in zip(best_masks, masks_s))

    # history: recorded on the integer cadence + final step
    cad = LD.history_cadence(kw["n_steps"])
    steps = [h["step"] for h in hist]
    assert steps[-1] == kw["n_steps"] - 1
    assert all(s % cad == 0 for s in steps[:-1])


@pytest.mark.slow
def test_paper_scale_jsc_searched_beats_random_sharded(jsc):
    """Paper-scale JSC-M-lite (64-32-5, A=2, F=4): the full pipeline —
    sharded population search, best-of-population selection, QAT
    retrain — beats seed-averaged random connectivity, and the sharded
    evaluation is bit-identical to single-device."""
    spec = PM.jsc_m_lite(degree=1)
    kw = dict(n_steps=200, n_seeds=4, phase_frac=0.6, eps2=2e-3)

    it = batch_iterator(jsc["train"], 256, seed=3)
    masks_s, scores_s, _, _ = LD.search_connectivity_population(
        jax.random.key(3), spec, it, mesh=SH.serving_mesh(2), **kw)
    it = batch_iterator(jsc["train"], 256, seed=3)
    masks_1, scores_1, _, _ = LD.search_connectivity_population(
        jax.random.key(3), spec, it, mesh=None, **kw)
    assert all(jnp.array_equal(a, b) for a, b in zip(masks_s, masks_1))
    assert jnp.array_equal(scores_s, scores_1)

    best_masks, _ = LD.select_best_masks(masks_s, scores_s)
    conn = LD.masks_to_conn(best_masks, spec)
    seeds = (10, 11, 12)
    rand_accs = [_train(spec, jsc, seed=s)[0] for s in seeds]
    opt_accs = [_train(spec, jsc, conn=conn, seed=s)[0] for s in seeds]
    assert np.mean(opt_accs) >= np.mean(rand_accs) - 0.01


@pytest.mark.slow
def test_paper_scale_mnist_searched_beats_random(mnist):
    """Paper-scale HDR/MNIST (784 -> 256-100-100-100-100-10, F=6,
    2-bit): sharded population search + best-of-population selection
    beats seed-averaged random connectivity.  Bit-identity of the
    sharded path is pinned by the fast test and the JSC slow test
    above; re-running this search single-device would double a
    multi-minute test for no new signal."""
    spec = PM.hdr(degree=1)
    it = batch_iterator(mnist["train"], 256, seed=3)
    masks, scores, _, _ = LD.search_connectivity_population(
        jax.random.key(3), spec, it, n_steps=100, n_seeds=4,
        mesh=SH.serving_mesh(2), phase_frac=0.6, eps2=2e-3)
    best_masks, _ = LD.select_best_masks(masks, scores)
    conn = LD.masks_to_conn(best_masks, spec)
    seeds = (10, 11)
    rand_accs = [_train(spec, mnist, seed=s)[0] for s in seeds]
    opt_accs = [_train(spec, mnist, conn=conn, seed=s)[0] for s in seeds]
    assert np.mean(opt_accs) >= np.mean(rand_accs) - 0.01


def test_paper_claim_add_reduces_lut_cost_iso_fanin():
    """Table II structure: same total fan-in, Add-variant needs
    exponentially fewer table entries and modeled LUT6s."""
    base = LD.ModelSpec(name="flat", in_features=784,
                        widths=(256, 100, 10), bits=2, fan_in=8)
    add = LD.ModelSpec(name="add", in_features=784,
                       widths=(256, 100, 10), bits=2, fan_in=4,
                       adder_width=2)
    assert base.table_entries > 10 * add.table_entries
    assert CM.lut_reduction(base, add) > 5.0


def test_cost_model_reproduces_paper_latency_ordering():
    """Fewer layers -> fewer cycles -> lower latency (Table IV trend)."""
    deep = PM.jsc_m_lite(degree=2)
    deep6 = PM.deeper(deep, 3)
    shallow = PM.jsc_m_lite_add2(degree=2)
    r_deep = CM.model_cost(deep6)
    r_shallow = CM.model_cost(shallow)
    assert r_shallow.cycles < r_deep.cycles
    assert r_shallow.latency_ns < r_deep.latency_ns


def test_sparse_ffn_lm_integration():
    """The paper's controller embedded in the LM substrate: fan-in hits
    the target while the loss still falls."""
    from repro.models import registry as R
    cfg = dataclasses.replace(
        R.get_config("qwen2.5-3b", smoke=True),
        sparse_ffn=True, sparse_fan_in=8, sparse_phase_T=15)
    init_state, step = R.make_train_step(cfg, remat=False)
    state = init_state(jax.random.key(0))
    jstep = jax.jit(step)
    rng = np.random.default_rng(0)
    # fixed batch: memorization is the fastest observable learning signal
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    losses = []
    for i in range(30):
        state, m = jstep(state, batch)
        losses.append(float(m["loss"]))
    theta = state["params"]["stacks"][0]["ffn"]["w_in_theta"]
    fan = np.asarray((theta > 0).sum(axis=1))
    assert (fan == 8).all()
    assert losses[-1] < losses[0]
