"""Standalone multi-device checks for core/distributed_loss.py, the
sharded data subsystem (data/sharded/, DESIGN.md §9), and the checkpoint
fault-tolerance harness (checkpoint/, DESIGN.md §10).

Run by tests/test_distributed_loss.py / tests/test_sharded_loader.py /
tests/test_fault_tolerance.py in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the tier-1 pytest
process pins the single real CPU device — see tests/conftest.py — and jax
locks the device count at first init, so multi-shard meshes need their own
process). ``loss``/``gradaccum`` assert the cross-shard GLOBAL-batch loss
and its dX/dY/dτ gradients are bit-close to the single-device fused loss at
the same global batch; ``sharded_data`` asserts the two-host loader
reassembles bit-exactly, device assembly places the right rows on the right
shards, and a checkpoint-resumed loader replays the identical batch
sequence. ``ckpt_fault`` is the kill-and-recover acceptance check: a
training run hard-killed MID-CHECKPOINT-WRITE (``ckpt_victim`` grandchild
process, ``os._exit`` via the write fault hook — SIGKILL-equivalent), with
its newest surviving checkpoint then bit-rotted, must auto-resume from the
newest VERIFIED step and replay the uninterrupted run's per-step losses
bit-exactly; ditto a SIGTERM-preempted run.

Usage:  python tests/distributed_checks.py
            {loss|gradaccum|sharded_data|ckpt_fault|ckpt_victim CKPT_DIR}
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import sys                                                       # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                                       # noqa: E402
import jax.numpy as jnp                                          # noqa: E402
import numpy as np                                               # noqa: E402

from repro.core import distributed_loss as dl                    # noqa: E402
from repro.core.contrastive import fused_kernel_loss             # noqa: E402
from repro.launch.mesh import make_mesh                          # noqa: E402


def _unit_rows(key, shape):
    z = jax.random.normal(key, shape, jnp.float32)
    return z / jnp.linalg.norm(z, axis=-1, keepdims=True)


def check_loss_equivalence():
    """Acceptance: data-axis size >= 2 mesh, both methods, loss and grads
    match the single-device fused loss at the same global batch (fp32)."""
    b, d = 256, 64
    kx, ky = jax.random.split(jax.random.key(7))
    x, y = _unit_rows(kx, (b, d)), _unit_rows(ky, (b, d))
    tau = jnp.asarray(0.31)

    def ref(x, y, tau):
        return fused_kernel_loss(x, y, tau, interpret=True)[0]

    ref_loss, ref_g = jax.value_and_grad(ref, argnums=(0, 1, 2))(x, y, tau)

    meshes = [
        make_mesh((8,), ("data",)),                  # pure data parallel
        make_mesh((4, 2), ("data", "model")),        # data x tensor
        make_mesh((2, 2, 2), ("pod", "data", "model")),  # multi-pod
    ]
    for mesh in meshes:
        for method in dl.METHODS:
            loss_fn = dl.make_global_loss_fn(mesh, method)

            def f(x, y, tau):
                return loss_fn(x, y, tau)[0]

            with mesh:
                loss, g = jax.jit(jax.value_and_grad(
                    f, argnums=(0, 1, 2)))(x, y, tau)
            tag = f"{dict(mesh.shape)}/{method}"
            np.testing.assert_allclose(loss, ref_loss, rtol=2e-6, atol=2e-6,
                                       err_msg=f"{tag} loss")
            for got, want, name in zip(g, ref_g, ("dX", "dY", "dtau")):
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                           err_msg=f"{tag} {name}")
            print(f"ok {tag}")

    # bf16 embeddings (fp32 accumulation inside the kernels): compare the
    # two distributed methods against the single-device fused loss on the
    # SAME bf16 inputs — rounding of the inputs is shared, paths must agree
    xb, yb = x.astype(jnp.bfloat16), y.astype(jnp.bfloat16)
    ref_loss16, ref_g16 = jax.value_and_grad(ref, argnums=(0, 1, 2))(
        xb, yb, tau)
    mesh = make_mesh((4, 2), ("data", "model"))
    for method in dl.METHODS:
        loss_fn = dl.make_global_loss_fn(mesh, method)
        with mesh:
            loss, g = jax.jit(jax.value_and_grad(
                lambda x, y, t: loss_fn(x, y, t)[0],
                argnums=(0, 1, 2)))(xb, yb, tau)
        np.testing.assert_allclose(loss, ref_loss16, rtol=1e-3, atol=1e-4,
                                   err_msg=f"bf16 {method} loss")
        np.testing.assert_allclose(
            g[0].astype(jnp.float32), ref_g16[0].astype(jnp.float32),
            rtol=2e-2, atol=1e-4, err_msg=f"bf16 {method} dX")
        print(f"ok bf16 {method}")


def check_gradaccum_composition():
    """The full Algorithm-1 step with the cross-shard loss (GradAccum x
    data-parallel x tensor-parallel under one jit) produces the same
    weight gradients as the single-device step at the same global batch."""
    from repro.configs import get_arch, smoke_dual_variant
    from repro.core.gradaccum import contrastive_step
    from repro.data import Tokenizer, caption_corpus, contrastive_batch, \
        world_for_tower
    from repro.models import dual_encoder as de

    cfg = smoke_dual_variant(get_arch("basic-s"))
    rng = np.random.default_rng(0)
    world = world_for_tower(rng, cfg.image_tower, n_classes=8, noise=0.2)
    tok = Tokenizer.train(caption_corpus(world, rng, 200), vocab_size=300)
    batch, _ = contrastive_batch(world, tok, 32, rng)
    batch = jax.tree.map(jnp.asarray, batch)
    params = de.init_params(cfg, jax.random.key(0))

    def enc_i(p, im):
        return de.encode_image(cfg, p, im)

    def enc_t(p, tx):
        return de.encode_text(cfg, p, tx)

    l_ref, _, g_ref = jax.jit(lambda p, b: contrastive_step(
        enc_i, enc_t, p, b, 2))(params, batch)

    mesh = make_mesh((4, 2), ("data", "model"))
    for method in dl.METHODS:
        loss_fn = dl.make_global_loss_fn(mesh, method)
        with mesh:
            l_dist, _, g_dist = jax.jit(lambda p, b: contrastive_step(
                enc_i, enc_t, p, b, 2, loss_fn=loss_fn,
                emb_sharding=dl.emb_sharding(mesh)))(params, batch)
        np.testing.assert_allclose(l_dist, l_ref, rtol=2e-5, atol=2e-6,
                                   err_msg=f"{method} loss")
        flat_ref = jax.tree_util.tree_leaves_with_path(g_ref)
        flat_dist = dict(jax.tree_util.tree_leaves_with_path(g_dist))
        for path, want in flat_ref:
            got = flat_dist[path]
            np.testing.assert_allclose(
                got, want, rtol=5e-4, atol=1e-5,
                err_msg=f"{method} grad {jax.tree_util.keystr(path)}")
        print(f"ok gradaccum {method}")


def check_sharded_data():
    """Acceptance (ISSUE-5): (1) the two simulated hosts' local shards
    concatenate BIT-EXACTLY to the single-host global batch, augmentation
    included; (2) ``device_put_global`` lays block h onto data shard h of
    an 8-way mesh with global content equal to the host-side batch; (3) a
    contrastive trainer run that checkpoints, stops, and resumes (loader
    state restored from checkpoint user-meta) reproduces the uninterrupted
    run's per-step losses exactly."""
    import tempfile
    import types

    from repro.data import make_world
    from repro.data.sharded import (HostLayout, ShardedLoader,
                                    default_augmentations, device_put_global,
                                    load_tokenizer)

    world = make_world(np.random.default_rng(3), n_classes=16)
    tok = load_tokenizer()
    aug = default_augmentations()

    # (1) two-host reassembly, clean and augmented
    for augment in ((), aug):
        hosts = [ShardedLoader(world, tok, 32, layout=HostLayout(2, h),
                               seed=11, augment=augment) for h in (0, 1)]
        oracle = ShardedLoader(world, tok, 32, layout=HostLayout(2, 0),
                               seed=11, augment=augment)
        for step in (0, 1, 5):
            want = oracle.global_batch_at(step)
            got = jax.tree.map(
                lambda *xs: np.concatenate(xs, axis=0),
                *[h.local_batch_at(step) for h in hosts])
            for path, a in jax.tree_util.tree_leaves_with_path(want):
                b = dict(jax.tree_util.tree_leaves_with_path(got))[path]
                np.testing.assert_array_equal(a, b)
    print("ok two-host reassembly (clean + augmented)")

    # (2) device assembly on an 8-way data mesh: block h -> shard h
    mesh = make_mesh((8,), ("data",))
    loader = ShardedLoader(world, tok, 32, layout=HostLayout(8, 0),
                           seed=11, augment=aug)
    host_batch = loader.global_batch_at(0)
    arrs = device_put_global(host_batch, mesh)
    img = arrs["images"]["image"]
    assert img.sharding.is_fully_addressable
    np.testing.assert_array_equal(np.asarray(img),
                                  host_batch["images"]["image"])
    shards = sorted(img.addressable_shards, key=lambda s: s.index[0].start)
    assert len(shards) == 8
    for h, s in enumerate(shards):
        block = ShardedLoader(world, tok, 32, layout=HostLayout(8, h),
                              seed=11, augment=aug).local_batch_at(0)
        np.testing.assert_array_equal(np.asarray(s.data),
                                      block["images"]["image"])
    print("ok device assembly block->shard")

    # (3) trainer-level resume: full run == stop@2 + resume, exact losses
    from repro.launch.train_distributed import train
    base = dict(arch="basic-s", smoke=True, objective="contrastive",
                steps=4, batch=64, seq=16, lr=1e-3, seed=0,
                sharding="basic_ws", remat="basic", model_parallel=1,
                num_micro=2, loss="chunked", augment="on", tokenizer="v1",
                log_every=100, ckpt_dir=None, ckpt_every=0, stop_after=None)
    full = train(types.SimpleNamespace(**base))
    with tempfile.TemporaryDirectory() as d:
        ck = dict(base, ckpt_dir=d)
        train(types.SimpleNamespace(**dict(ck, stop_after=2)))
        resumed = train(types.SimpleNamespace(**ck))
    np.testing.assert_allclose(resumed, full[2:], rtol=1e-5)
    print("ok trainer resume replays the batch sequence")


_TRAIN_BASE = dict(arch="basic-s", smoke=True, objective="contrastive",
                   steps=6, batch=64, seq=16, lr=1e-3, seed=0,
                   sharding="basic_ws", remat="basic", model_parallel=1,
                   num_micro=2, loss="chunked", augment="on", tokenizer="v1",
                   log_every=100, ckpt_dir=None, ckpt_every=0,
                   stop_after=None)

_VICTIM_KILL_STEP = 4     # die during the 2nd file-write of this step's save
_VICTIM_EXIT = 17


def run_ckpt_victim(ckpt_dir):
    """Grandchild process of the ckpt_fault check: train with async
    per-step checkpointing, then die by ``os._exit`` (no cleanup — the
    SIGKILL/preemption stand-in) in the middle of writing step
    ``_VICTIM_KILL_STEP``'s checkpoint, leaving a torn ``.tmp_ckpt_*``
    behind. Never returns."""
    import types

    from repro.checkpoint import faults, io
    from repro.launch.train_distributed import train

    orig = io.write_snapshot

    def dying_write(directory, step, arrs, treedef, meta=None):
        if step == _VICTIM_KILL_STEP:
            # allow one leaf file, then os._exit on the next write: the
            # tmp dir is left torn, exactly like a mid-save preemption
            with faults.exit_during_write(after=1, code=_VICTIM_EXIT):
                return orig(directory, step, arrs, treedef, meta=meta)
        return orig(directory, step, arrs, treedef, meta=meta)

    io.write_snapshot = dying_write
    train(types.SimpleNamespace(**dict(_TRAIN_BASE, ckpt_dir=ckpt_dir,
                                       ckpt_every=1)))
    raise SystemExit("victim survived training — kill hook never fired")


def check_ckpt_fault():
    """Acceptance (ISSUE-6): (1) a run hard-killed mid-checkpoint-write
    leaves completed steps plus a torn tmp dir; (2) after the newest
    completed checkpoint is additionally bit-rotted, ``--resume auto``
    lands on the older verified step (GC'ing the torn tmp) and the resumed
    run replays the uninterrupted run's per-step losses BIT-EXACTLY on the
    8-device mesh; (3) a SIGTERM-preempted run writes a final sync
    checkpoint after the in-flight step and resumes bit-exactly too."""
    import glob
    import subprocess
    import tempfile
    import types

    from repro import checkpoint as ckpt
    from repro.checkpoint import faults
    from repro.launch.train_distributed import train

    full = train(types.SimpleNamespace(**_TRAIN_BASE))
    print(f"uninterrupted run: {len(full)} steps")

    with tempfile.TemporaryDirectory() as d:
        # (1) kill a training run in the middle of a checkpoint write
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "ckpt_victim", d],
            capture_output=True, text=True, timeout=900, env=dict(os.environ))
        assert proc.returncode == _VICTIM_EXIT, (
            f"victim exit {proc.returncode}\n--- stdout ---\n{proc.stdout}"
            f"\n--- stderr ---\n{proc.stderr[-3000:]}")
        torn = glob.glob(os.path.join(d, ".tmp_ckpt_*"))
        assert torn, "kill mid-write must leave a torn tmp dir"
        assert ckpt.latest_step(d) == _VICTIM_KILL_STEP - 1
        print(f"ok victim killed mid-write of step {_VICTIM_KILL_STEP} "
              f"(torn tmp: {os.path.basename(torn[0])})")

        # (2) bit-rot the newest completed checkpoint: auto-resume must
        # skip it to the older verified step and GC the torn tmp
        faults.flip_byte(d, _VICTIM_KILL_STEP - 1)
        good = _VICTIM_KILL_STEP - 2
        assert ckpt.latest_verified_step(d, gc=False) == good
        resumed = train(types.SimpleNamespace(**dict(_TRAIN_BASE,
                                                     ckpt_dir=d)))
        assert not glob.glob(os.path.join(d, ".tmp_ckpt_*")), \
            "resume must GC the torn tmp dir"
        np.testing.assert_array_equal(
            np.asarray(resumed, np.float64),
            np.asarray(full[good:], np.float64),
            err_msg="killed+resumed losses must be bit-exact vs "
                    "uninterrupted")
        print(f"ok resume skipped corrupt step {_VICTIM_KILL_STEP - 1} -> "
              f"{good}; {len(resumed)} resumed losses bit-exact")

    # (3) SIGTERM preemption: final sync checkpoint + bit-exact resume
    with tempfile.TemporaryDirectory() as d:
        pre = train(types.SimpleNamespace(**dict(_TRAIN_BASE, ckpt_dir=d,
                                                 preempt_after=2)))
        assert len(pre) == 2 and ckpt.latest_verified_step(d) == 2
        resumed = train(types.SimpleNamespace(**dict(_TRAIN_BASE,
                                                     ckpt_dir=d)))
        np.testing.assert_array_equal(
            np.asarray(pre + resumed, np.float64),
            np.asarray(full, np.float64),
            err_msg="SIGTERM-preempted + resumed losses must be bit-exact")
    print("ok SIGTERM preemption checkpoint + bit-exact resume")


def check_retrieval():
    """Acceptance (ISSUE-9): the mesh-sharded similarity→top-k serving
    path picks the same indices as the stable-argsort oracle and is
    BIT-IDENTICAL to the single-device kernel on 4-device, 8-device, and
    2x4 pod×data meshes —
    including exact ties and duplicate rows straddling shard boundaries,
    ragged N (last shard partially padded), n so small that whole shards
    are dead padding, and bf16 inputs. Then: the ZeroShotService wired to
    retrieval='sharded' classifies identically to the 'fused' service, a
    prepared gallery is uploaded once, and k>n clamps / k<1 raises on the
    sharded path."""
    from repro.kernels.similarity_topk import ops as topk_ops
    from repro.kernels.similarity_topk import ref as topk_ref
    from repro.serving import retrieval as rtv

    b, d, k = 9, 32, 7
    kx = jax.random.key(23)
    x = _unit_rows(kx, (b, d))
    meshes = [
        make_mesh((4,), ("data",)),
        make_mesh((8,), ("data",)),
        make_mesh((2, 4), ("pod", "data")),   # multi-axis linear index
    ]

    def oracle(x, c, kk):
        v, i = topk_ref.similarity_topk_ref(jnp.asarray(x, jnp.float32),
                                            jnp.asarray(c, jnp.float32), kk)
        return np.asarray(v), np.asarray(i)

    rng = np.random.default_rng(5)
    for mesh in meshes:
        s = int(np.prod([mesh.shape[a] for a in mesh.shape]))
        tag = dict(mesh.shape)
        # n sweeps: ragged tails, exact multiples, and n < S*k (k=7, S*64
        # n_local floor -> every shard but the first is 100% padding)
        for n in (40, 257, 64 * s, 64 * s + 1, 1000):
            # duplicates + exact ties EVERYWHERE, including straddling
            # shard boundaries: every row drawn from a 17-row dictionary,
            # so each boundary [n_local*r - 1, n_local*r] pair collides
            # with near-certainty and every top-k is a tie-break decision
            dic = np.asarray(_unit_rows(jax.random.key(n), (17, d)))
            c = dic[rng.integers(0, 17, n)]
            kk = min(k, n)
            _, want_i = oracle(x, c, kk)
            # values: the one-sweep kernel on the same inputs (the einsum
            # oracle's fp32 dot may round differently from the kernel's
            # tiles by an ulp, depending on the host's XLA CPU kernels)
            fused_v, fused_i = topk_ops.similarity_topk(
                x, jnp.asarray(c), kk, interpret=True)
            sm = rtv.shard_matrix(jnp.asarray(c), mesh)
            got_v, got_i = rtv.sharded_similarity_topk(x, sm, kk,
                                                       interpret=True)
            np.testing.assert_array_equal(
                np.asarray(got_i), want_i,
                err_msg=f"{tag} n={n}: sharded indices != oracle")
            np.testing.assert_array_equal(
                np.asarray(got_i), np.asarray(fused_i),
                err_msg=f"{tag} n={n}: sharded indices != fused")
            np.testing.assert_array_equal(
                np.asarray(got_v), np.asarray(fused_v),
                err_msg=f"{tag} n={n}: sharded values != fused")
        print(f"ok sharded==oracle {tag} (ties/duplicates/ragged)")

    # bf16 inputs: compare against the single-device kernel on the SAME
    # bf16 arrays (shared input rounding; both paths accumulate fp32)
    mesh = meshes[1]
    n = 700
    c = _unit_rows(jax.random.key(41), (n, d))
    xb, cb = x.astype(jnp.bfloat16), c.astype(jnp.bfloat16)
    want_v, want_i = topk_ops.similarity_topk(xb, cb, k, interpret=True)
    sm = rtv.shard_matrix(cb, mesh)
    got_v, got_i = rtv.sharded_similarity_topk(xb, sm, k, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    print("ok sharded==fused on bf16 inputs")

    # k validation at the op level
    sm = rtv.shard_matrix(jnp.asarray(_unit_rows(jax.random.key(2),
                                                 (300, d))), mesh)
    for bad_k in (0, -3, 301):
        try:
            rtv.sharded_similarity_topk(x, sm, bad_k, interpret=True)
            raise AssertionError(f"k={bad_k} must raise")
        except ValueError:
            pass
    print("ok op-level k validation")

    # service level: sharded classify == fused classify, upload-once
    # gallery, k clamping, k<1 rejection
    import dataclasses as dc

    from repro.configs import get_arch, smoke_variant
    from repro.data import Tokenizer, caption_corpus, world_for_tower
    from repro.data.synthetic import render_images
    from repro.models import dual_encoder as de
    from repro.serving import ZeroShotService

    cfg = get_arch("basic-s")
    cfg = dc.replace(cfg, image_tower=smoke_variant(cfg.image_tower),
                     text_tower=smoke_variant(cfg.text_tower), embed_dim=32)
    rng = np.random.default_rng(0)
    world = world_for_tower(rng, cfg.image_tower, n_classes=10, noise=0.2)
    tok = Tokenizer.train(caption_corpus(world, rng, 300), vocab_size=400)
    params = de.init_params(cfg, jax.random.key(0))
    imgs = render_images(world, rng.integers(0, 10, 6), rng)

    with ZeroShotService(cfg, params, tok, max_delay_ms=1.0,
                         retrieval="fused") as svc:
        ref_res = svc.classify(imgs, world.class_names, k=5)
        gal = svc.embed_images(imgs)
    with ZeroShotService(cfg, params, tok, max_delay_ms=1.0,
                         retrieval="sharded") as svc:
        res = svc.classify(imgs, world.class_names, k=5)
        np.testing.assert_array_equal(res.indices, ref_res.indices)
        np.testing.assert_array_equal(res.values, ref_res.values)
        # k > n_classes clamps to n (10), never errors on the sharded path
        wide = svc.classify(imgs, world.class_names, k=64)
        assert wide.indices.shape == (6, 10)
        np.testing.assert_array_equal(wide.indices[:, :5], res.indices)
        try:
            svc.classify(imgs, world.class_names, k=0)
            raise AssertionError("k=0 must raise")
        except ValueError:
            pass
        # prepared gallery: one upload, many retrieves, clamped k
        handle = svc.prepare_gallery(gal)
        v1, i1 = svc.retrieve(["a photo"], handle, k=64)
        v2, i2 = svc.retrieve(["a photo"], handle, k=64)
        assert i1.shape == (1, 6)       # clamped to the 6-row gallery
        np.testing.assert_array_equal(i1, i2)
        snap = svc.metrics.snapshot()
        assert snap["counters"]["serve/gallery_uploads"] == 1
        shares = [key for key in snap["histograms"]
                  if key.startswith("serve/retrieval_shard_share")]
        assert shares, snap["histograms"].keys()
    print("ok service-level sharded parity + gallery handle + k clamps")


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "loss"
    if mode == "ckpt_victim":
        run_ckpt_victim(sys.argv[2])
    assert jax.device_count() >= 8, jax.devices()
    {"loss": check_loss_equivalence,
     "gradaccum": check_gradaccum_composition,
     "sharded_data": check_sharded_data,
     "ckpt_fault": check_ckpt_fault,
     "retrieval": check_retrieval}[mode]()
    print(f"PASS {mode}")
