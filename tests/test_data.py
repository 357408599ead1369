"""Data substrate: tokenizer properties, synthetic world, pipeline.

Hypothesis-based tokenizer fuzzing lives in test_data_properties.py (behind
``importorskip``) so this module collects on bare environments.
"""
import threading
import time

import numpy as np
import pytest

from repro.data import (HostLayout, ShardedLoader, Tokenizer, caption_corpus,
                        classification_prompts, contrastive_batch, host_rng,
                        make_world)
from repro.data.pipeline import Prefetcher
from repro.data.synthetic import render_images


_CACHE = {}


def _tok():
    if "wt" not in _CACHE:
        rng = np.random.default_rng(0)
        world = make_world(rng, n_classes=16)
        _CACHE["wt"] = (world, Tokenizer.train(
            caption_corpus(world, rng, 500), vocab_size=512))
    return _CACHE["wt"]


def test_tokenizer_vocab_and_determinism():
    _, tok = _tok()
    assert tok.vocab_size <= 512
    a = tok.encode("a photo of a red cat")
    b = tok.encode("a photo of a red cat")
    assert a == b
    assert all(0 <= i < tok.vocab_size for i in a)


def test_encode_truncation_preserves_eos():
    """Regression: truncating a long caption at max_len used to drop the
    EOS; it must stay the final token (ids[:max_len-1] + [EOS])."""
    from repro.data.tokenizer import BOS, EOS
    _, tok = _tok()
    long_caption = " ".join(["red cat blue dog green bird"] * 10)
    full = tok.encode(long_caption, max_len=512)
    assert len(full) < 512 and full[-1] == EOS      # untruncated keeps EOS
    for max_len in (8, 16, 31):
        ids = tok.encode(long_caption, max_len=max_len)
        assert len(ids) == max_len
        assert ids[0] == BOS and ids[-1] == EOS, (max_len, ids[-4:])
        # the truncated body is a prefix of the untruncated encoding
        assert ids[:-1] == full[:max_len - 1]
    # no specials: plain prefix truncation, no EOS to preserve
    raw = tok.encode(long_caption, max_len=8, add_special=False)
    assert len(raw) == 8 and raw[-1] != EOS


def test_contrastive_stream_rejects_indivisible_global_batch():
    """Regression: global_batch % n_hosts != 0 used to silently shrink the
    global batch (local = B // n_hosts); it must raise instead."""
    from repro.data.pipeline import contrastive_stream
    world, tok = _tok()
    with np.testing.assert_raises_regex(ValueError, "divisible"):
        contrastive_stream(world, tok, 10, n_hosts=3)
    # the divisible case still streams
    pf = contrastive_stream(world, tok, 8, n_hosts=2, host_id=1)
    batch = next(pf)
    pf.close()
    assert batch["images"]["image"].shape[0] == 4


def test_pad_batch_shapes():
    _, tok = _tok()
    toks, mask = tok.pad_batch([[2, 5, 6], [2, 5]], max_len=8)
    assert toks.shape == (2, 8) and mask.shape == (2, 8)
    assert mask[0].sum() == 3 and mask[1].sum() == 2


def test_world_determinism_and_separability():
    """Same seed -> identical data; images of the same class are closer to
    their class mean than to other classes (so transfer is learnable)."""
    rng1, rng2 = np.random.default_rng(7), np.random.default_rng(7)
    w1, w2 = make_world(rng1), make_world(rng2)
    np.testing.assert_array_equal(w1.concept_vecs, w2.concept_vecs)

    world, tok = _tok()
    rng = np.random.default_rng(1)
    batch, cls = contrastive_batch(world, tok, 64, rng)
    raw = batch["images"]["image"]                 # (64, H, W, C) raw pixels
    assert raw.shape[1:] == (world.image_size, world.image_size,
                             world.channels)
    imgs = raw.reshape(raw.shape[0], -1)
    # class centroids
    cents = {c: imgs[cls == c].mean(0) for c in set(cls.tolist())
             if (cls == c).sum() > 1}
    correct = 0
    total = 0
    for i, c in enumerate(cls):
        if c not in cents:
            continue
        dists = {cc: np.linalg.norm(imgs[i] - v) for cc, v in cents.items()}
        correct += (min(dists, key=dists.get) == c)
        total += 1
    assert correct / total > 0.6


def test_classification_prompts_cover_all_classes():
    world, tok = _tok()
    prompts = classification_prompts(world, tok)
    assert prompts["tokens"].shape[0] == world.n_classes


def test_host_rng_streams_disjoint():
    a = host_rng(0, 0, 0).integers(0, 1 << 30, 8)
    b = host_rng(0, 1, 0).integers(0, 1 << 30, 8)
    c = host_rng(0, 0, 1).integers(0, 1 << 30, 8)
    assert not np.array_equal(a, b) and not np.array_equal(a, c)
    np.testing.assert_array_equal(a, host_rng(0, 0, 0).integers(0, 1 << 30, 8))


def test_prefetcher_yields_deterministic_batches():
    world, tok = _tok()

    def make(step):
        rng = host_rng(3, 0, step)
        batch, _ = contrastive_batch(world, tok, 8, rng)
        return batch

    pf = Prefetcher(make, depth=2)
    b0 = next(pf)
    next(pf)
    pf.close()
    expect, _ = contrastive_batch(world, tok, 8, host_rng(3, 0, 0))
    np.testing.assert_array_equal(b0["texts"]["tokens"],
                                  expect["texts"]["tokens"])


def test_prefetcher_close_ends_iteration_instead_of_hanging():
    """Regression: ``__next__`` after ``close()`` used to block forever on
    the drained queue; it must raise StopIteration promptly, and close()
    must be idempotent."""
    import threading
    import time

    pf = Prefetcher(lambda step: step, depth=2)
    next(pf)
    pf.close()
    pf.close()                    # idempotent
    # drain whatever was prefetched, then the stream must END
    t0 = time.time()
    tail = list(pf)
    assert time.time() - t0 < 5.0
    assert len(tail) <= 2         # at most `depth` buffered batches
    with np.testing.assert_raises(StopIteration):
        next(pf)

    # a consumer already blocked in next() must wake up after close()
    pf2 = Prefetcher(lambda step: step, depth=2)
    for _ in range(3):
        next(pf2)                 # queue momentarily drained
    got = {}

    def consume():
        try:
            while True:
                next(pf2)
        except StopIteration:
            got["stopped"] = True

    t = threading.Thread(target=consume)
    t.start()
    time.sleep(0.1)
    pf2.close()
    t.join(timeout=5.0)
    assert got.get("stopped") and not t.is_alive()


def test_prefetcher_surfaces_worker_crash():
    """A make_batch exception must re-raise at the consumer (not hang the
    training loop on an empty queue with a dead producer)."""
    def bad(step):
        raise ValueError(f"boom at {step}")

    pf = Prefetcher(bad, depth=2)
    with np.testing.assert_raises(ValueError):
        next(pf)
    pf.close()                    # still idempotent after a crash


def _stacked_render(world, cls, rng):
    """The stacked formula render_images must match byte for byte: a 3-D
    ``z @ camera``, its float32 cast, then the patch-grid transpose."""
    b = cls.shape[0]
    g = world.image_size // world.patch_size
    ps, c = world.patch_size, world.channels
    z = world.concept_vecs[cls]
    z = z[:, None, :] + world.noise * rng.standard_normal(
        (b, world.n_patches, z.shape[-1]))
    pix = (z @ world.camera).astype(np.float32)
    pix = pix.reshape(b, g, g, ps, ps, c).transpose(0, 1, 3, 2, 4, 5)
    return np.ascontiguousarray(pix.reshape(b, g * ps, g * ps, c))


@pytest.mark.parametrize("batch", [1, 17])
@pytest.mark.parametrize("image_size,patch_size,channels",
                         [(16, 4, 3), (32, 8, 3), (224, 16, 3), (28, 4, 1)])
def test_render_images_byte_identical_to_stacked_formula(
        image_size, patch_size, channels, batch):
    """The one-pass render (2-D gemm per chunk, cast and transpose into the
    output; 17 images of 224² span two chunks) gives the stacked formula's
    bytes and leaves the rng where the stacked formula leaves it."""
    world = make_world(np.random.default_rng(image_size), n_classes=16,
                       image_size=image_size, patch_size=patch_size,
                       channels=channels)
    cls = np.random.default_rng(batch).integers(0, 16, batch)
    rng_new, rng_old = np.random.default_rng(5), np.random.default_rng(5)
    got = render_images(world, cls, rng_new)
    want = _stacked_render(world, cls, rng_old)
    assert got.dtype == np.float32 and got.flags.c_contiguous
    assert got.shape == want.shape == (batch, image_size, image_size,
                                       channels)
    assert got.tobytes() == want.tobytes()
    assert rng_new.standard_normal() == rng_old.standard_normal()


def test_global_batch_at_one_host_is_local_batch():
    world, tok = _tok()
    loader = ShardedLoader(world, tok, 12, layout=HostLayout(1), seed=4)
    for step in (0, 3):
        got, want = loader.global_batch_at(step), loader.local_batch_at(step)
        assert got["images"]["image"].tobytes() == \
            want["images"]["image"].tobytes()
        for key in ("tokens", "attn_mask"):
            assert got["texts"][key].tobytes() == want["texts"][key].tobytes()


def test_prefetcher_makes_each_step_once_under_a_slow_consumer():
    """Regression: the worker used to call make_batch(step) again each
    time its put on the full queue timed out (0.5 s), so a consumer slower
    than that saw every step made over and over."""
    calls = []

    def make(step):
        calls.append(step)
        return step

    pf = Prefetcher(make, depth=1)
    got = []
    for _ in range(3):
        got.append(next(pf))
        time.sleep(0.7)               # longer than the put's timeout
    got.append(next(pf))
    pf.close()
    assert got == [0, 1, 2, 3]
    assert calls == list(range(len(calls)))


def test_prefetcher_close_during_pending_put_ends_stream():
    made = threading.Event()

    def make(step):
        if step == 1:
            made.set()                # the queue (depth 1) holds step 0
        return step

    pf = Prefetcher(make, depth=1)
    assert made.wait(timeout=5.0)
    time.sleep(0.1)                   # the worker is inside its put
    t0 = time.time()
    pf.close()
    assert time.time() - t0 < 2.0
    assert not pf._thread.is_alive()
    assert list(pf) == [0]
    with pytest.raises(StopIteration):
        next(pf)
