"""Batched embedding similarity scorer (MiniLM on device).

Replaces the reference's per-word synchronous word2vec lookups
(backend.py:45, 303-317) with fixed-shape batched MiniLM encodes: guesses
and answers tokenize on host, pad into one of a few static (batch, seq)
buckets, embed in a single device call, and score as a cosine dot — the
BASELINE.json "1k concurrent guesses coalesced onto HBM" path when driven
through the serving queue.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cassmantle_tpu.chaos import fault_point
from cassmantle_tpu.config import MiniLMConfig
from cassmantle_tpu.models.minilm import MiniLMEncoder
from cassmantle_tpu.models.weights import (
    convert_minilm,
    init_params_cached,
    maybe_load,
)
from cassmantle_tpu.ops.embed_table import (
    EMBED_TABLE_PATH,
    EmbedTable,
    embed_table_disabled,
    normalize_key,
    read_header,
    table_signature,
    weights_fingerprint,
)
from cassmantle_tpu.serving import integrity
from cassmantle_tpu.serving.integrity import finite_verdict
from cassmantle_tpu.utils.compile_cache import (
    enable_compile_cache,
    param_cache_path,
)
from cassmantle_tpu.utils.logging import get_logger, metrics
from cassmantle_tpu.utils.profiling import block_timer, named_jit
from cassmantle_tpu.utils.tokenizers import Tokenizer, load_tokenizer

log = get_logger("scorer")


def _pick_bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class EmbeddingScorer:
    """Host-facing wrapper owning params, tokenizer, and jitted encode."""

    def __init__(
        self,
        cfg: MiniLMConfig,
        weights_dir=None,
        seq_len: int = 16,
        batch_buckets: Sequence[int] = (8, 64, 256, 1024),
        embed_cache_size: int = 2048,
        table="auto",
    ) -> None:
        self.cfg = cfg
        # Text -> unit-embedding LRU: /compute_score re-embeds the
        # round's FIXED answer words on every request, so a hit halves
        # the per-guess device batch (and duplicate answers within one
        # batch collapse to a single device row). Embeddings are
        # content-addressed by text — nothing ever invalidates.
        # Untracked short-hold leaf lock (docs/STATIC_ANALYSIS.md):
        # dict updates only, the device encode runs OUTSIDE it.
        self._embed_cache: OrderedDict = OrderedDict()
        self._embed_cache_size = embed_cache_size
        self._embed_cache_lock = threading.Lock()
        self.seq_len = min(seq_len, cfg.max_positions)
        self.batch_buckets = tuple(batch_buckets)
        self.tokenizer: Tokenizer = load_tokenizer(
            weights_dir, "minilm", cfg.vocab_size
        )
        model = MiniLMEncoder(cfg)
        sample_ids = jnp.zeros((1, self.seq_len), dtype=jnp.int32)
        sample_mask = jnp.ones((1, self.seq_len), dtype=jnp.int32)
        enable_compile_cache()

        def load_params() -> None:
            """Load/init the encoder tree; re-run by reload_params()
            during a device-loss rebuild (serving/device_recovery.py)."""
            self.params = (
                maybe_load(weights_dir, "minilm.safetensors",
                           lambda t: convert_minilm(t, cfg.num_layers),
                           "minilm")
                or init_params_cached(
                    model, 7, sample_ids, sample_mask,
                    cache_path=param_cache_path("minilm", cfg))
            )

        self._param_loader = load_params
        load_params()
        # the encode jit also returns the per-row integrity verdict
        # (serving/integrity.py): computed in-jit, transferred with the
        # embeddings — no extra dispatch or sync

        def encode_impl(params, ids, mask):
            with jax.named_scope("scorer_encode"):
                emb = model.apply(params, ids, mask)
            return emb, finite_verdict(emb)

        self._encode = named_jit(encode_impl, "scorer_encode")
        # roofline attribution (obs/costmodel.py): an encoder forward
        # costs ~2·N(params) FLOPs per token; resolved lazily from the
        # committed cost model (production MiniLM) or this tree
        self._flops_per_row = None
        # rung 0 of the scoring ladder: the committed int8 wordlist
        # table (ops/embed_table.py). ``table="auto"`` arms it only
        # when the artifact's signature matches THIS scorer's config +
        # wordlist + weights identity, so a test-config scorer or a
        # stale artifact silently keeps the LRU/device path. Pass an
        # EmbedTable to inject, or False/None to disable outright.
        if table == "auto":
            self.table = self._autoload_table(weights_dir)
        elif isinstance(table, EmbedTable):
            if table.dim != cfg.hidden_size:
                raise ValueError(
                    f"embed table dim {table.dim} != scorer hidden "
                    f"size {cfg.hidden_size}")
            self.table = table
        else:
            self.table = None
        if self.table is not None:
            metrics.gauge("scorer.table_rows", len(self.table))

    def reload_params(self) -> None:
        """Device-loss rebuild (serving/device_recovery.py): re-load
        the encoder tree (fingerprint-verified, utils/checkpoint.py)
        onto the fresh runtime. The embed LRU and the int8 table hold
        HOST arrays — content-addressed by text, runtime-independent —
        so neither needs invalidation; params re-enter the encode jit
        as arguments, so nothing recompiles."""
        self._param_loader()

    def _autoload_table(self, weights_dir):
        try:
            header = read_header(EMBED_TABLE_PATH)
        except (OSError, ValueError):
            return None
        from cassmantle_tpu.server.assets import load_wordlist

        expect = table_signature(
            self.cfg, self.seq_len,
            [normalize_key(w) for w in load_wordlist()],
            weights_fingerprint(weights_dir))
        if header["signature"] != expect:
            # info, not warning: every non-production scorer config
            # (tests, tools) lands here by design
            log.info(
                "embed table not armed: committed signature %s != "
                "expected %s", header["signature"], expect)
            return None
        return EmbedTable.load(EMBED_TABLE_PATH,
                               expected_signature=expect)

    def _row_flops(self) -> float:
        """Analytic FLOPs per encoded row (seq_len tokens)."""
        if self._flops_per_row is None:
            from cassmantle_tpu.obs import costmodel

            self._flops_per_row = costmodel.flops_per_item(
                "scorer",
                costmodel.scorer_signature(self.cfg, self.seq_len),
                tracer=lambda: 2.0 * costmodel.params_count(self.params)
                * self.seq_len,
            ) or 0.0
        return self._flops_per_row

    # -- host-side batching ----------------------------------------------
    def _tokenize_batch(self, texts: Sequence[str], batch: int
                        ) -> Tuple[np.ndarray, np.ndarray]:
        ids = np.full((batch, self.seq_len), self.tokenizer.pad_id,
                      dtype=np.int32)
        mask = np.zeros((batch, self.seq_len), dtype=np.int32)
        for i, text in enumerate(texts):
            toks = self.tokenizer.encode(text)[: self.seq_len]
            if not toks:
                toks = [self.tokenizer.pad_id]
            # lint: ignore[host-sync] — toks is a host token list, not a device array
            ids[i, : len(toks)] = np.asarray(toks, dtype=np.int32) % (
                self.cfg.vocab_size
            )
            mask[i, : len(toks)] = 1
        return ids, mask

    def _embed_device(self, texts: Sequence[str]
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """The uncached device path: (n,) texts -> ((n, D) unit
        embeddings, (n,) validity) via padded buckets (one encode per
        bucket chunk). Validity is the in-jit verdict unioned with a
        host finiteness check of the transferred rows — all-True under
        the integrity kill switch."""
        n = len(texts)
        batch = _pick_bucket(n, self.batch_buckets)
        out_chunks = []
        ok_chunks = []
        for start in range(0, n, batch):
            chunk = texts[start : start + batch]
            ids, mask = self._tokenize_batch(chunk, batch)
            # device-synchronized stage span: for a /compute_score
            # request this is the trace's leaf — the MiniLM encode the
            # whole guess batch waited on. flops_est covers the PADDED
            # batch (the device computes pad rows too)
            with block_timer("scorer.encode_s",
                             flops_est=self._row_flops() * batch,
                             pipeline="scorer") as sink:
                fault_point("device.lost", peer="scorer")
                emb, verdict = self._encode(
                    self.params, jnp.asarray(ids), jnp.asarray(mask))
                sink.append(emb)
            # lint: ignore[host-sync] — one sync per dispatched chunk, not per text
            rows = integrity.poison(np.asarray(emb)[: len(chunk)],
                                    peer="scorer")
            out_chunks.append(rows)
            if integrity.integrity_disabled():
                ok_chunks.append(np.ones(len(chunk), dtype=bool))
            else:
                # the verdict rides the completed dispatch; judging the
                # transferred rows too catches host-side corruption
                # lint: ignore[host-sync] — one sync per dispatched chunk, not per text
                okj = np.asarray(verdict).astype(bool)[: len(chunk)]
                ok_chunks.append(okj & np.isfinite(rows).all(axis=-1))
        return (np.concatenate(out_chunks, axis=0),
                np.concatenate(ok_chunks, axis=0))

    def embed(self, texts: Sequence[str]) -> np.ndarray:
        """(n,) texts -> (n, D) unit embeddings via the scoring ladder:
        int8 table -> LRU -> device.

        Rung 0 is the committed wordlist table (when armed and the
        ``CASSMANTLE_NO_EMBED_TABLE`` kill switch is off): in-table
        texts are served as host int8 dequants with zero device work,
        counted by ``scorer.table_hits``; the rest count
        ``scorer.table_oov`` and fall through. The LRU/device rungs are
        unchanged and bit-exact when the table is skipped: rows already
        in the LRU (or duplicated within this call) never reach the
        device — only the unique uncached texts form the padded encode
        batch. ``scorer.embed_cache_misses`` therefore counts device
        rows actually embedded; ``scorer.embed_cache_hits`` counts rows
        served from the LRU. The returned array is always freshly
        assembled — callers may mutate it."""
        n = len(texts)
        if n == 0:
            return np.zeros((0, self.cfg.hidden_size), dtype=np.float32)
        out = np.zeros((n, self.cfg.hidden_size), dtype=np.float32)
        table = self.table \
            if self.table is not None and not embed_table_disabled() \
            else None
        if table is not None:
            rest: List[int] = []
            hits = 0
            for i, text in enumerate(texts):
                row = table.lookup(text)
                if row is None:
                    rest.append(i)
                else:
                    out[i] = row
                    hits += 1
            metrics.inc("scorer.table_hits", hits)
            metrics.inc("scorer.table_oov", len(rest))
        else:
            rest = list(range(n))
        miss_rows: "OrderedDict[str, list]" = OrderedDict()
        with self._embed_cache_lock:
            for i in rest:
                text = texts[i]
                emb = self._embed_cache.get(text)
                if emb is not None:
                    self._embed_cache.move_to_end(text)
                    out[i] = emb
                else:
                    miss_rows.setdefault(text, []).append(i)
        if miss_rows:
            fresh, ok = self._embed_device(list(miss_rows))
            bad_members: List[int] = []
            with self._embed_cache_lock:
                for row, valid, (text, idxs) in zip(
                        fresh, ok, miss_rows.items()):
                    if not valid:
                        # an invalid row never enters the LRU (a cached
                        # NaN would poison every later hit); the output
                        # rows stay NaN so downstream scoring fails
                        # loudly per pair, not silently as zeros
                        out[idxs] = np.nan
                        bad_members.extend(idxs)
                        continue
                    out[idxs] = row
                    if self._embed_cache_size > 0:
                        # copy: a row VIEW would pin the whole encode
                        # batch array alive for the entry's lifetime
                        self._embed_cache[text] = row.copy()
                        self._embed_cache.move_to_end(text)
                        while len(self._embed_cache) > \
                                self._embed_cache_size:
                            self._embed_cache.popitem(last=False)
            if bad_members:
                integrity.note_invalid("scorer", "encode",
                                       sorted(bad_members))
        metrics.inc("scorer.texts", n)
        metrics.inc("scorer.embed_cache_misses", len(miss_rows))
        metrics.inc("scorer.embed_cache_hits", len(rest) - len(miss_rows))
        return out

    def similarity(self, pairs: Sequence[Tuple[str, str]]) -> np.ndarray:
        """[(guess, answer)] -> cosine similarity per pair, one device
        batch for all guesses+answers."""
        if not pairs:
            return np.zeros((0,), dtype=np.float32)
        texts = [g for g, _ in pairs] + [a for _, a in pairs]
        emb = self.embed(texts)
        n = len(pairs)
        return np.sum(emb[:n] * emb[n:], axis=-1)

    def table_scores(self, pairs: Sequence[Tuple[str, str]]):
        """Rung-0 fused scoring for the service fast path:
        [(guess, answer)] -> (scores, served-mask) via the int8 table,
        or None when no table is armed / the kill switch is set. Pairs
        with ``served[i]`` True completed with zero device dispatches;
        the caller runs the full ladder for the rest only."""
        if self.table is None or embed_table_disabled():
            return None
        return self.table.score_pairs(list(pairs))

    def pin_answers(self, words: Sequence[str]) -> int:
        """Pin round answers into the armed table at promotion time:
        words not already in the table are embedded once through the
        normal LRU/device ladder, quantized with the committed scheme,
        and overlaid — so by the time guesses arrive, every (guess,
        answer) pair over the game vocabulary is rung-0-servable.
        Returns the number of rows pinned (``scorer.table_pins``)."""
        if self.table is None or embed_table_disabled():
            return 0
        todo: List[str] = []
        seen = set()
        for w in words:
            key = normalize_key(w)
            if key and key not in seen and not self.table.contains(key):
                seen.add(key)
                todo.append(key)
        if not todo:
            return 0
        rows = self.embed(todo)
        for w, row in zip(todo, rows):
            self.table.pin(w, row)
        return len(todo)

    def most_similar(self, word: str, candidates: Sequence[str],
                     top_k: int = 5) -> List[Tuple[str, float]]:
        """k nearest candidate words by embedding cosine (the reference's
        word2vec ``most_similar`` surface, backend.py:297-301, over an
        explicit candidate list instead of a fixed gensim vocabulary).

        Rides :meth:`embed`, so candidate ranking climbs the same
        table -> LRU -> device ladder: in-vocabulary candidates are
        served from the int8 table and only OOV text pays a padded
        device batch.
        """
        if not candidates:
            return []
        emb = self.embed([word] + list(candidates))
        sims = emb[1:] @ emb[0]
        order = np.argsort(-sims)[:top_k]
        # lint: ignore[host-sync] — sims is a host np array (embed returns host)
        return [(candidates[i], float(sims[i])) for i in order]

    async def similarity_async(self, pairs) -> np.ndarray:
        """engine.scoring.SimilarityFn adapter."""
        return self.similarity(list(pairs))
