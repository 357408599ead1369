"""ZeroShotService: the public zero-shot inference API (DESIGN.md §6, §13).

Ties the three layers of the embedding subsystem together over a BASIC dual
encoder (paper §3):

  classify(images, class_names)  — image tower via the micro-batcher, class
      matrix via the registry (computed once per label space + checkpoint,
      persisted), similarity→top-k over the class axis with the learned
      temperature — the (b, n_classes) logit matrix never exists.
  embed(tower, ...)              — raw unit-norm embeddings, micro-batched.
  retrieve(queries, gallery)     — text→gallery top-k with the same path
      (inv_tau=1: retrieval convention, no temperature sharpening).

One flag — ``retrieval`` — selects how the top-k sweep runs (§13):

  "fused"     single-device fused Pallas kernel (the PR-2 path; default),
  "sharded"   exact mesh-sharded sweep: class/gallery rows split over the
              mesh data axes, per-shard kernels + top-k-of-top-k combine —
              bit-identical to "fused" (serving/retrieval/sharded.py),
  "twostage"  coarse centroid prune → exact rerank for the long tail; the
              centroid index is cached through the registry keyed on
              (matrix key, version), so checkpoint/tokenizer refreshes
              invalidate it by construction. ``nprobe`` trades recall for
              latency; ``nprobe="all"`` is exact.

Class matrices and galleries are prepared ONCE per artifact: classify keeps
a device-resident (mode-shaped) copy per registry (key, version); retrieve
accepts a ``GalleryHandle`` from ``prepare_gallery`` (and memoizes raw
arrays as a convenience) so repeated calls pay zero host→device upload.

``eval.zero_shot.evaluate_with_service`` and ``examples/serving_demo.py``
are the first two consumers.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.dual import DualEncoderConfig
from repro.eval.zero_shot import DEFAULT_TEMPLATES, class_embeddings
from repro.kernels.similarity_topk import ops as topk_ops
from repro.models import dual_encoder as de
from repro.obs import export as obs_export
from repro.obs import health as obs_health
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serving import retrieval as rtv
from repro.serving.embed.batcher import DEFAULT_BUCKETS, MicroBatcher
from repro.serving.embed.registry import (ClassEmbeddingRegistry,
                                          checkpoint_fingerprint)

RETRIEVAL_MODES = ("fused", "sharded", "twostage")


@dataclasses.dataclass(frozen=True)
class ClassifyResult:
    """Top-k classification output of ``ZeroShotService.classify``."""
    values: np.ndarray        # (b, k) fp32 similarity/temperature logits
    indices: np.ndarray       # (b, k) int32 class ids, ties to lower id
    class_names: tuple        # the label space, for decoding
    version: int              # registry artifact version that classified

    def top_names(self, row: int):
        """Class-name strings of row ``row``'s top-k, best first."""
        return [self.class_names[i] for i in self.indices[row]]


@dataclasses.dataclass(frozen=True)
class GalleryHandle:
    """A gallery prepared for the service's retrieval mode: device-resident
    (pre-sharded for "sharded", centroid-indexed for "twostage"), so every
    ``retrieve`` against it pays zero upload and zero index build. Obtain
    via ``ZeroShotService.prepare_gallery``."""
    data: object                       # jax.Array | ShardedMatrix | ndarray
    n: int                             # gallery rows
    mode: str                          # retrieval mode it was prepared for
    index: Optional[rtv.CentroidIndex] = None   # "twostage" only


class ZeroShotService:
    """Zero-shot inference front door (DESIGN.md §6): micro-batched
    embedding (MicroBatcher) + memoized class matrices
    (ClassEmbeddingRegistry) + the similarity→top-k sweep selected by
    ``retrieval``, behind ``classify`` / ``embed_images`` / ``embed_texts``
    / ``retrieve``. Context-manager friendly (stops the batcher on exit).

    retrieval: "fused" | "sharded" | "twostage" (module docstring).
    mesh: the device mesh for "sharded" (default: a 1-D data mesh over all
    local devices). nprobe: "twostage" blocks probed per query (None ≡
    "all" ≡ exact). index_blocks: centroid count (default ≈ √n).
    All three modes share one ``obs`` registry (``self.metrics``, also fed
    by the batcher) and one tracer, so ``stats()``/``obs.report`` show the
    whole serving path.

    SLO (DESIGN.md §14.3): ``latency_slo_s`` arms an ``SLOTracker`` —
    every ``classify``/``retrieve`` call's wall time feeds a windowed p99
    vs the target plus an error-budget burn gauge (``serve/slo_*``
    series), and readiness flips False while the windowed budget is
    exhausted. ``serve_metrics()`` exposes it all live over HTTP.
    """

    def __init__(self, cfg: DualEncoderConfig, params, tok, *,
                 templates: Sequence[str] = DEFAULT_TEMPLATES,
                 text_len: int = 16,
                 registry_dir: Optional[str] = None,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 max_delay_ms: float = 2.0,
                 request_timeout_s: float = 60.0,
                 precision="f32",
                 interpret: Optional[bool] = None,
                 retrieval: str = "fused",
                 mesh=None,
                 nprobe: Union[int, str, None] = None,
                 index_blocks: Optional[int] = None,
                 tracer: Optional[obs_trace.Tracer] = None,
                 autostart: bool = True,
                 latency_slo_s: Optional[float] = None,
                 slo_objective: float = 0.99,
                 slo_window: int = 256):
        if retrieval not in RETRIEVAL_MODES:
            raise ValueError(f"retrieval={retrieval!r} not in "
                             f"{RETRIEVAL_MODES}")
        self.cfg = cfg
        self.params = params
        self.tok = tok
        self.templates = tuple(templates)
        self.text_len = int(text_len)
        self.interpret = interpret
        self.retrieval = retrieval
        self.mesh = mesh
        self.nprobe = nprobe
        self.index_blocks = index_blocks
        # params fingerprint + tokenizer artifact hash: new weights OR a
        # retrained vocab both invalidate cached class matrices (§9)
        self.checkpoint_tag = checkpoint_fingerprint(params, tok)
        # 1/tau from the learned log-temperature (paper §3: A = X·Yᵀ/tau)
        self.inv_tau = float(jnp.exp(-params["log_tau"]))

        self.metrics = obs_metrics.Registry()
        self.tracer = tracer if tracer is not None else obs_trace.Tracer()
        self._request_ids = itertools.count()   # the spans' ``request``
        enc_i = jax.jit(lambda p, im: de.encode_image(cfg, p, im,
                                                      precision=precision))
        enc_t = jax.jit(lambda p, tx: de.encode_text(cfg, p, tx,
                                                     precision=precision))
        self.batcher = MicroBatcher(
            {"image": lambda im: enc_i(self.params, im),
             "text": lambda tx: enc_t(self.params, tx)},
            buckets=buckets, max_delay_ms=max_delay_ms,
            request_timeout_s=request_timeout_s, autostart=autostart,
            registry=self.metrics)
        self.registry = ClassEmbeddingRegistry(self._compute_class_matrix,
                                               cache_dir=registry_dir)
        self._cm_device: dict = {}       # (key, version, mode) -> prepared
        self._gallery_memo = collections.OrderedDict()  # id -> (ref, handle)
        self._gallery_memo_cap = 4
        self.slo = None
        if latency_slo_s is not None:
            self.slo = obs_health.SLOTracker(
                target_s=float(latency_slo_s), objective=slo_objective,
                window=slo_window, registry=self.metrics, name="serve")

    # -- embedding ---------------------------------------------------------
    def embed_images(self, images, *, wait: bool = True):
        """images: raw (b, H, W, C) pixels matching the image tower's
        geometry (or a dict payload, e.g. {'image': ...}) — the serving
        image-preprocessing path feeds the tower's patchify frontend.
        Returns (b, D) unit-norm fp32 — or the future when wait=False."""
        payload = images if isinstance(images, dict) else \
            {"image": np.asarray(images, np.float32)}
        fut = self.batcher.submit_many("image", payload)
        return self._result(fut) if wait else fut

    def embed_texts(self, texts, *, wait: bool = True):
        """texts: list of strings (tokenized here) or a pre-tokenized
        {'tokens', 'attn_mask'} payload. Returns (b, D) — or the future."""
        if not isinstance(texts, dict):
            ids = [self.tok.encode(t, max_len=self.text_len) for t in texts]
            tokens, mask = self.tok.pad_batch(ids, max_len=self.text_len)
            texts = {"tokens": tokens, "attn_mask": mask}
        fut = self.batcher.submit_many("text", texts)
        return self._result(fut) if wait else fut

    def _result(self, fut):
        if not self.batcher.running:
            self.batcher.flush_now()   # thread-free (autostart=False) path
        # the per-request deadline bounds the wait: classify/embed_* can
        # never hang indefinitely on a wedged flush thread
        return np.asarray(fut.result(timeout=self.batcher.request_timeout))

    # -- classification ----------------------------------------------------
    def classify(self, images, class_names: Sequence[str], *,
                 templates: Optional[Sequence[str]] = None,
                 k: int = 5) -> ClassifyResult:
        k = int(k)
        if k < 1:
            raise ValueError(f"k={k} must be >= 1")
        class_names = tuple(class_names)
        templates = tuple(templates) if templates is not None \
            else self.templates
        t_req = time.perf_counter()
        rid = next(self._request_ids)
        try:
            with obs_trace.span(self.tracer, "serve/classify", request=rid,
                                n_classes=len(class_names), k=k,
                                mode=self.retrieval):
                iemb_fut = self.embed_images(images, wait=False)
                cm = self.registry.get(class_names, templates,
                                       self.checkpoint_tag,
                                       embed_dim=self.cfg.embed_dim)
                data = self._class_data(cm)
                index = self.registry.get_centroid_index(
                    cm, n_blocks=self.index_blocks) \
                    if self.retrieval == "twostage" else None
                iemb = self._result(iemb_fut)
                vals, idx = self._topk(iemb, data, len(class_names),
                                       min(k, len(class_names)),
                                       inv_tau=self.inv_tau, index=index,
                                       request=rid)
        finally:
            if self.slo is not None:
                self.slo.observe(time.perf_counter() - t_req)
        return ClassifyResult(vals, idx, class_names, cm.version)

    # -- retrieval ---------------------------------------------------------
    def prepare_gallery(self, gallery_emb) -> GalleryHandle:
        """Upload + shape ``gallery_emb`` (m, D) for the service's
        retrieval mode ONCE (device put / mesh shard / centroid index).
        Repeated ``retrieve`` calls against the returned handle do no
        host→device transfer and no index build — the fix for the old
        per-call ``jnp.asarray(gallery_emb)`` upload."""
        n = int(np.shape(gallery_emb)[0])
        mode = self.retrieval
        self.metrics.counter("serve/gallery_uploads").inc()
        with obs_trace.span(self.tracer, "serve/prepare_gallery",
                            n=n, mode=mode):
            index = None
            if mode == "sharded":
                data = rtv.shard_matrix(gallery_emb, self.mesh)
            elif mode == "twostage":
                data = np.asarray(gallery_emb, np.float32)
                index = rtv.build_centroid_index(
                    data, n_blocks=self.index_blocks)
            else:
                data = jnp.asarray(gallery_emb)
        return GalleryHandle(data, n, mode, index)

    def retrieve(self, queries: Sequence[str], gallery, *, k: int = 5,
                 nprobe: Union[int, str, None] = None):
        """Text→gallery retrieval: top-k gallery rows per query by cosine
        similarity. gallery: a ``GalleryHandle`` from ``prepare_gallery``
        (preferred — upload-once), or a raw (m, D) unit-norm array
        (prepared on first sight, memoized by object identity so repeated
        calls with the same array also upload once). Returns
        (values (q, k), indices (q, k)); k is clamped to the gallery size.
        nprobe overrides the service default for this call ("twostage")."""
        k = int(k)
        if k < 1:
            raise ValueError(f"k={k} must be >= 1")
        handle = gallery if isinstance(gallery, GalleryHandle) \
            else self._memo_gallery(gallery)
        if handle.mode != self.retrieval:
            raise ValueError(f"gallery prepared for mode {handle.mode!r}; "
                             f"service runs {self.retrieval!r} — call "
                             f"prepare_gallery again")
        t_req = time.perf_counter()
        rid = next(self._request_ids)
        try:
            with obs_trace.span(self.tracer, "serve/retrieve", request=rid,
                                n=handle.n, k=k, mode=self.retrieval):
                qemb = self.embed_texts(list(queries))
                return self._topk(qemb, handle.data, handle.n,
                                  min(k, handle.n), inv_tau=1.0,
                                  index=handle.index, nprobe=nprobe,
                                  request=rid)
        finally:
            if self.slo is not None:
                self.slo.observe(time.perf_counter() - t_req)

    def _memo_gallery(self, gallery_emb) -> GalleryHandle:
        """Bounded identity-keyed memo for raw-array galleries (the memo
        holds the reference, so the id stays valid while cached)."""
        key = id(gallery_emb)
        hit = self._gallery_memo.get(key)
        if hit is not None and hit[0] is gallery_emb:
            self._gallery_memo.move_to_end(key)
            self.metrics.counter("serve/gallery_memo_hits").inc()
            return hit[1]
        handle = self.prepare_gallery(gallery_emb)
        self._gallery_memo[key] = (gallery_emb, handle)
        while len(self._gallery_memo) > self._gallery_memo_cap:
            self._gallery_memo.popitem(last=False)
        return handle

    # -- the top-k sweep ---------------------------------------------------
    def _topk(self, q, data, n: int, k: int, *, inv_tau, index=None,
              nprobe=None, request=None):
        """Dispatch the (b, k) sweep per the retrieval mode, recording the
        §13 serving telemetry: per-stage ``serve/retrieval_latency_s``,
        ``serve/retrieval_prune_ratio`` (twostage: candidates/n) and
        ``serve/retrieval_shard_share`` (sharded: max per-shard share of
        the winners — 1/S ≈ balanced, →1 ≈ one hot shard)."""
        mode = self.retrieval
        t0 = time.perf_counter()
        with obs_trace.span(self.tracer, f"serve/topk_{mode}",
                            request=request, n=n, k=k):
            if mode == "sharded":
                vals, idx = rtv.sharded_similarity_topk(
                    jnp.asarray(q), data, k, inv_tau=inv_tau,
                    interpret=self.interpret)
                shares = rtv.shard_winner_shares(idx, data)
                self.metrics.histogram(
                    "serve/retrieval_shard_share",
                    buckets=obs_metrics.RATIO_BUCKETS,
                    mode=mode).observe(float(shares.max()))
            elif mode == "twostage":
                vals, idx, info = rtv.two_stage_topk(
                    np.asarray(q), data, index, k,
                    nprobe=self.nprobe if nprobe is None else nprobe,
                    inv_tau=inv_tau, interpret=self.interpret)
                self.metrics.histogram(
                    "serve/retrieval_prune_ratio",
                    buckets=obs_metrics.RATIO_BUCKETS,
                    mode=mode).observe(info["prune_ratio"])
                for stage in ("coarse", "gather", "rerank"):
                    self.metrics.histogram(
                        "serve/retrieval_latency_s", mode=mode,
                        stage=stage).observe(info[f"{stage}_s"])
                if self.tracer is not None:
                    self.tracer.instant("serve/twostage_info", **info)
            else:
                vals, idx = topk_ops.similarity_topk(
                    jnp.asarray(q), data, k, inv_tau=inv_tau,
                    interpret=self.interpret)
        self.metrics.histogram("serve/retrieval_latency_s", mode=mode,
                               stage="total").observe(
            time.perf_counter() - t0)
        return np.asarray(vals), np.asarray(idx)

    def _class_data(self, cm):
        """The mode-shaped, device-resident copy of a registry artifact,
        prepared once per (key, version): refreshes re-prepare by
        construction (new version → new cache key)."""
        ck = (cm.key, cm.version, self.retrieval)
        hit = self._cm_device.get(ck)
        if hit is None:
            if self.retrieval == "sharded":
                hit = rtv.shard_matrix(cm.matrix, self.mesh)
            elif self.retrieval == "twostage":
                hit = np.asarray(cm.matrix, np.float32)
            else:
                hit = jnp.asarray(cm.matrix)
            self._cm_device[ck] = hit
        return hit

    # -- internals ---------------------------------------------------------
    def _compute_class_matrix(self, class_names, templates):
        """Registry compute path: batched prompt ensembling through the
        text tower, via the SAME ``eval.zero_shot.class_embeddings`` the
        offline eval uses — one code path, one artifact."""
        def encode(texts):
            fut = self.batcher.submit_many("text", texts)
            if not self.batcher.running:
                self.batcher.flush_now()
            return jnp.asarray(
                fut.result(timeout=self.batcher.request_timeout))
        return class_embeddings(encode, self.tok, class_names, templates,
                                text_len=self.text_len)

    def stats(self) -> dict:
        """Service-wide stats: the batcher's dict-shaped counters + the
        class-embedding registry's hit/miss counts (legacy shape), plus
        ``metrics`` — the shared ``obs.metrics.Registry`` snapshot (batcher
        latency/occupancy AND the serve/retrieval_* series; DESIGN.md §11,
        §13.4)."""
        out = {"batcher": dict(self.batcher.stats),
               "compiled_shapes": len(self.batcher.compiled_shapes()),
               "registry": dict(self.registry.stats),
               "retrieval_mode": self.retrieval,
               "metrics": self.metrics.snapshot()}
        if self.slo is not None:
            out["slo"] = self.slo.status()
        return out

    def serve_metrics(self, *, port: int = 0,
                      host: str = "127.0.0.1") -> obs_export.MetricsServer:
        """Start a live HTTP endpoint over this service's registry:
        ``/metrics`` (Prometheus), ``/healthz`` (SLO readiness when a
        ``latency_slo_s`` was set — 503 while the error budget is
        exhausted), ``/snapshot.json``. Localhost-only by default; the
        caller owns the returned server (``stop()`` it)."""
        return obs_export.MetricsServer(
            self.metrics,
            health=self.slo.status if self.slo is not None else None,
            host=host, port=port).start()

    def close(self):
        self.batcher.stop()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
