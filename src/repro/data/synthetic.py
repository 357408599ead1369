"""Synthetic open-vocabulary image-text world (the ALIGN/JFT simulation).

repro=2 gate: the real 6.6B-pair dataset is proprietary, so we build a
*controllable* joint distribution whose zero-shot transfer is measurable:

- A latent concept space: ``n_classes`` concepts, each a unit vector in R^k
  plus attribute words drawn from a template grammar.
- Images: RAW PIXELS (b, H, W, C). Per patch, concept vector + noise is
  pushed through a fixed random "camera" map into ``patch_size²·C`` pixel
  values and the patch grid is assembled into the image — the inverse of
  the model's patchify frontend, so class evidence survives patchification
  exactly.
- Captions: templated natural-ish text ("a photo of a red tabby cat") using
  the concept's name words + sampled attributes — noisy, like alt-text.
- JFT analog: (image, class-id) pairs over the same concepts with multi-label
  class-name strings, enabling the paper's pretrain→contrastive recipe (§8).

Held-out concepts (never seen in contrastive training) measure
open-vocabulary generalization; benchmark tables are built on this.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

ADJECTIVES = ["red", "blue", "green", "small", "large", "striped", "spotted",
              "shiny", "old", "young", "wild", "fluffy", "sleek", "bright"]
NOUNS = ["cat", "dog", "bird", "fish", "tree", "car", "boat", "house",
         "flower", "horse", "plane", "train", "apple", "chair", "clock",
         "river", "mountain", "beetle", "lamp", "guitar", "violin", "drum",
         "bridge", "tower", "island", "lizard", "rabbit", "wolf", "bear",
         "eagle", "shark", "whale", "rose", "oak", "pine", "truck", "bicycle",
         "kettle", "mirror", "ladder"]
TEMPLATES = ["a photo of a {} {}", "the {} {}", "{} {} in the wild",
             "a picture showing a {} {}", "my {} {}", "one {} {}, outdoors"]


@dataclasses.dataclass
class World:
    """The synthetic joint distribution: latent concept vectors, the fixed
    camera map that renders them to pixels, class-name strings, and the
    image geometry every render matches (see module docstring)."""
    concept_vecs: np.ndarray      # (n_classes, k)
    camera: np.ndarray            # (k, patch_size²·channels) latent -> pixels
    class_names: List[str]
    image_size: int
    patch_size: int
    channels: int = 3
    noise: float = 0.35

    @property
    def n_classes(self):
        return self.concept_vecs.shape[0]

    @property
    def n_patches(self):
        return (self.image_size // self.patch_size) ** 2


def make_world(rng: np.random.Generator, n_classes=64, latent=32,
               image_size=16, patch_size=4, channels=3,
               noise=0.35) -> World:
    """Concepts are COMPOSITIONAL: class 'red cat' = v(red) + v(cat) in the
    latent space, so a model that learns the factors from seen classes can
    zero-shot transfer to unseen adjective-noun combinations — the toy analog
    of open-vocabulary generalization."""
    adj_vecs = rng.standard_normal((len(ADJECTIVES), latent))
    noun_vecs = rng.standard_normal((len(NOUNS), latent))
    names, vecs = [], []
    for i in range(n_classes):
        ai = (i * 5 + i // len(ADJECTIVES)) % len(ADJECTIVES)
        ni = i % len(NOUNS)
        names.append(f"{ADJECTIVES[ai]} {NOUNS[ni]}")
        vecs.append(adj_vecs[ai] + noun_vecs[ni])
    v = np.stack(vecs)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    pix = patch_size * patch_size * channels
    cam = rng.standard_normal((latent, pix)) / np.sqrt(latent)
    return World(v, cam, names, image_size, patch_size, channels, noise)


def world_for_tower(rng: np.random.Generator, tower, n_classes=64,
                    latent=32, noise=0.35) -> World:
    """A World whose image geometry matches a vision ArchConfig (the image
    tower of a dual encoder): same image_size/patch_size/channels, so
    rendered images feed the tower's patchify frontend directly."""
    return make_world(rng, n_classes=n_classes, latent=latent,
                      image_size=tower.image_size,
                      patch_size=tower.patch_size,
                      channels=tower.channels, noise=noise)


# float64 camera product per chunk of render_images: small enough to stay
# in cache, large enough that each gemm is worth waking the BLAS threads
_RENDER_CHUNK_BYTES = 1 << 24


def render_images(world: World, cls: np.ndarray, rng: np.random.Generator):
    """cls: (b,) int -> RAW images (b, H, W, C) float32: per-patch noisy
    concept latents through the camera map, assembled on the patch grid.

    One pass: the float64 camera product runs as one 2-D gemm per chunk of
    images (about ``_RENDER_CHUNK_BYTES`` of float64 product each), and its
    float32 cast lands straight in the output through the patch-grid view.
    The result is a pure function of ``(world, cls, rng)``, byte-identical
    to the stacked ``(z @ camera).astype(float32)`` then transpose."""
    b = cls.shape[0]
    g = world.image_size // world.patch_size
    ps, c = world.patch_size, world.channels
    p, k = world.n_patches, world.concept_vecs.shape[-1]
    z = rng.standard_normal((b, p, k))
    z *= world.noise
    z += world.concept_vecs[cls][:, None, :]
    out = np.empty((b, g * ps, g * ps, c), np.float32)
    grid = out.reshape(b, g, ps, g, ps, c)
    step = max(1, _RENDER_CHUNK_BYTES // (8 * p * world.camera.shape[-1]))
    for i in range(0, b, step):
        n = min(step, b - i)
        pix = z[i:i + n].reshape(n * p, k) @ world.camera
        grid[i:i + n] = pix.reshape(n, g, g, ps, ps, c).transpose(
            0, 1, 3, 2, 4, 5)
    return out


def render_captions(world: World, cls: np.ndarray, rng: np.random.Generator,
                    class_names: Optional[List[str]] = None) -> List[str]:
    """Noisy alt-text analog: one templated caption per class id in
    ``cls``, templates sampled from the grammar."""
    names = class_names or world.class_names
    out = []
    for c in cls:
        t = TEMPLATES[rng.integers(len(TEMPLATES))]
        out.append(t.format(*names[int(c)].split(" ", 1)))
    return out


def caption_corpus(world: World, rng: np.random.Generator, n=2000):
    """n sampled captions over the world's classes (tokenizer training /
    per-run corpora; the committed artifact trains on ``grammar_corpus``)."""
    cls = rng.integers(0, world.n_classes, n)
    return render_captions(world, cls, rng)


def grammar_corpus() -> List[str]:
    """EVERY caption the template grammar can produce: all adjective ×
    noun × template combinations, in a fixed deterministic order. No rng,
    no World — the closure of the caption language — so a tokenizer trained
    on it covers any world's captions and retrains bit-identically
    (the corpus behind ``artifacts/tokenizer_v1.json``)."""
    return [t.format(a, n) for a in ADJECTIVES for n in NOUNS
            for t in TEMPLATES]


def contrastive_batch(world: World, tok, batch: int, rng: np.random.Generator,
                      text_len=16, classes: Optional[np.ndarray] = None):
    """Returns ({'images': {...}, 'texts': {...}}, cls)."""
    pool = classes if classes is not None else np.arange(world.n_classes)
    cls = pool[rng.integers(0, len(pool), batch)]
    imgs = render_images(world, cls, rng)
    caps = render_captions(world, cls, rng)
    ids = [tok.encode(c, max_len=text_len) for c in caps]
    tokens, mask = tok.pad_batch(ids, max_len=text_len)
    return ({"images": {"image": imgs},
             "texts": {"tokens": tokens, "attn_mask": mask}}, cls)


def classification_prompts(world: World, tok, text_len=16,
                           template="a photo of a {} {}"):
    """CLIP-style class prompts for zero-shot eval."""
    ids = [tok.encode(template.format(*n.split(" ", 1)), max_len=text_len)
           for n in world.class_names]
    tokens, mask = tok.pad_batch(ids, max_len=text_len)
    return {"tokens": tokens, "attn_mask": mask}


def jft_batch(world: World, batch: int, rng: np.random.Generator,
              classes: Optional[np.ndarray] = None):
    """Labeled pretraining pairs (paper §8): (raw image, class id)."""
    pool = classes if classes is not None else np.arange(world.n_classes)
    cls = pool[rng.integers(0, len(pool), batch)]
    return {"image": render_images(world, cls, rng),
            "labels": cls.astype(np.int32)}, cls
