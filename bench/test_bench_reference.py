"""The plain reference against the program's own path, on the CPU at the
smoke size (float32 everywhere, so the two agree to rounding)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench.reference import model as M
from bench.reference import serve as RS
from bench.reference import train as RT

SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def tiny():
    import os
    return H.load_config("tiny", dirs=[os.path.join(H.BENCH, "testdata")])


@pytest.fixture(scope="module")
def program(tiny):
    from bench.program import program_config
    from repro.models import dual_encoder as de
    pc = program_config(tiny)
    return pc, de.init_params(pc, jax.random.key(SEED))


@pytest.fixture(scope="module")
def batch(tiny):
    from repro.data import load_tokenizer, world_for_tower
    from repro.data.sharded import HostLayout, ShardedLoader
    from bench.program import program_config
    pc = program_config(tiny)
    world = world_for_tower(np.random.default_rng(SEED), pc.image_tower,
                            n_classes=16, noise=0.2)
    loader = ShardedLoader(world, load_tokenizer("v1"), 16,
                           layout=HostLayout(n_hosts=1), seed=SEED,
                           text_len=16)
    return loader.global_batch_at(0)


def test_weights_are_the_programs(tiny, program):
    _, prog = program
    ref = RT.init_params_device(tiny, SEED)
    pl = jax.tree_util.tree_flatten_with_path(prog)[0]
    rl = jax.tree_util.tree_flatten_with_path(ref)[0]
    assert [jax.tree_util.keystr(k) for k, _ in pl] == \
        [jax.tree_util.keystr(k) for k, _ in rl]
    for (_, a), (_, b) in zip(pl, rl):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_loss_and_gradient_match_gradaccum(tiny, program, batch):
    from repro.core.gradaccum import contrastive_step
    from repro.models import dual_encoder as de
    pc, params = program

    def enc_i(p, x):
        return de.encode_image(pc, p, x, precision="f32")

    def enc_t(p, x):
        return de.encode_text(pc, p, x, precision="f32")

    loss, _, grads = jax.jit(lambda p, b: contrastive_step(
        enc_i, enc_t, p, b, 2))(params, batch)
    ref = RT.Reference(tiny, block_rows=8)
    r_loss, r_grads = ref.loss_and_grad(RT.init_params_device(tiny, SEED),
                                        batch)
    assert r_loss == pytest.approx(float(loss), rel=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(r_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-6)


def test_one_adafactorw_step_matches(tiny, program):
    from repro.launch.steps import make_optimizer
    _, params = program
    opt, o = make_optimizer(), tiny["optimizer"]
    grads = jax.tree.map(lambda p: jnp.sin(3.0 * p) + 0.01, params)
    up, state = opt.update(grads, opt.init(params), params, o["lr"])
    want = jax.tree.map(jnp.add, params, up)
    got, rstate = RT.adafactorw_update(
        params, grads, RT.adafactorw_init(params, o["factored_threshold"]),
        o["lr"], b1=o["beta1"], b2=o["beta2"], eps=o["eps"],
        wd=o["weight_decay"], clip=o["clip_threshold"],
        thr=o["factored_threshold"])
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    for a, b in zip(jax.tree.leaves(state.v_row),
                    jax.tree.leaves(jax.tree.map(
                        lambda v: v[0], rstate["v"],
                        is_leaf=lambda v: isinstance(v, tuple)))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_classify_topk_matches_the_service(tiny, program):
    from bench import traffic as T
    from bench.drivers.classify import prompts
    from repro.data import load_tokenizer
    from repro.serving import ZeroShotService
    pc, params = program
    tok = load_tokenizer("v1")
    names = T.class_names({"count": 24, "first": ["red", "blue", "old"],
                           "second": ["small", "wild", "shiny"],
                           "nouns": ["cat", "dog", "tree"]})
    imgs = T.image_pool(12, 16, 3, SEED)
    with ZeroShotService(pc, params, tok, precision="f32") as svc:
        res = svc.classify(imgs, names, k=5)
        ids = prompts(tok, names, svc.templates, svc.text_len)
        n_t = len(svc.templates)
    logits = RS.logits(tiny, SEED, imgs, *ids, n_t, block_rows=8)
    rank_gap, value_gap = RS.answer_gaps(logits, res.values, res.indices)
    assert rank_gap < 1e-4 and value_gap < 1e-4
    low = RS.logits(tiny, SEED, imgs, *ids, n_t, num=M.BF16, block_rows=8)
    assert np.max(np.abs(low - logits)) > 10 * value_gap
