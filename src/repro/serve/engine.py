"""Distributed continuous-batching engine on the comm layer.

A fixed pool of batch *slots* shares one KV cache allocation tracked by a
:class:`repro.serve.kv.KVLedger` — the ragged ``DistBag`` extents picture,
per-request lengths over uniform capacity tiles.  Finished sequences free
their slot and the next queued request is prefilled into it.

Engine phases map onto the comm layer (see ``repro.core``'s "Serving on the
comm layer" notes):

  * **admission-time prefill** runs each admitted prompt as one chunk
    through ``lm.decode_step(prefill=True)``, one program call per admitted
    slot: a one-row program that reads and writes only that slot's rows of
    the donated cache (the slot a traced index), so resident rows cost
    nothing; under an ``sp_ring`` recipe the chunk's attention is the
    sequence-parallel ring plan — the ``Allgatherv``-over-seq-shards phase;
  * **decode** runs the GSPMD path (single host / recipe);
  * given a ``(data, model)`` mesh and ``microbatches``, both run instead
    through the explicit tensor-parallel step of
    :mod:`repro.serve.tp_decode`, whose per-layer reductions are issued as
    non-blocking ``Pending`` collectives staggered behind the next
    microbatch's compute (``Iallreduce``/``Iallgather`` per layer, nothing
    on the critical path — what ``--serve`` dry-runs gate).

The single-host engine (no mesh) is the reference the distributed
configuration is tested against: same per-row cache semantics, same greedy
sampling.  The two are different compilations of the same model math, so
their logits agree to rounding, not bit for bit.

Tracing: the host loop marks its phases with ``jax.profiler.TraceAnnotation``
spans, which land in a profiler trace on the device events' clock and cost
about a microsecond each while no profiler records.  The phase spans tile one
iteration of :meth:`Engine.run`:

  * ``engine.admit`` (args ``admitted``, ``queued``, ``kv_valid_bytes``):
    a round that admits at least one request — queue pops, ledger, slot
    state reset;
  * ``engine.prefill_launch`` (``rows``, ``bucket``, ``calls``,
    ``padded_tokens``): the prefill feeds built, copied to the device and
    the step program called ``calls`` times; ``padded_tokens`` is the token
    rows those calls run beyond the ``rows`` prompts' own (padding to the
    bucket, and idle slots where a call runs every slot);
  * ``engine.decode_launch`` (``rows``): the decode feed built, copied and
    the decode step dispatched;
  * ``engine.fetch`` (``bytes``): the picking program launched on the
    decode program's logits and the next token ids it returns pulled to the
    host, which waits here for both programs; ``bytes`` is what the copy
    moved;
  * ``engine.sample`` (``rows``): the picked tokens appended, ledger
    advanced, finished slots released.

Besides, ``engine.queued`` (``rid``) runs per request from :meth:`submit` to
its admission.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import lm
from repro.models.sharding import use_recipe
from repro.serve.kv import KVLedger

__all__ = ["ServeConfig", "Engine"]

# families whose decode-path attention accepts multi-token chunks exactly
# (position-masked reads over a length-tracked cache); recurrent/windowed
# state (ssm, hybrid) and capacity-factor dispatch (moe) prefill per-token
_CHUNK_FAMILIES = ("dense", "audio", "mla", "vlm")


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 512
    batch_slots: int = 4
    temperature: float = 0.0  # 0 = greedy
    eos_token: int = 1
    seed: int = 0


@dataclasses.dataclass
class _Slot:
    request_id: int | None = None
    tokens: list = dataclasses.field(default_factory=list)
    remaining: int = 0
    next_embed: Any = None  # (m,) f32 — embeds-model feed for the next step


def _np_sinusoidal(ids, d: int):
    """Deterministic token-id featurizer for embeds-input models: the
    engine-side stand-in for a codec/projection front end.  Distinct ids map
    to distinct embeddings, so generation actually depends on the prompt
    (the all-zeros-embedding bug fed every request the same silence)."""
    ids = np.asarray(ids, np.float32)
    half = d // 2
    freq = np.exp(-np.log(10000.0) * np.arange(half, dtype=np.float32) / half)
    ang = ids[..., None] * freq
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1).astype(np.float32)


def _kv_bytes_per_pos(cfg) -> int:
    """Cache bytes one sequence position costs across all layers (0 for
    families whose state does not grow with length)."""
    item = jnp.dtype(cfg.act_dtype).itemsize
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        return 2 * cfg.n_layers * cfg.n_kv * cfg.head_dim * item
    if cfg.family == "mla":
        return cfg.n_layers * (cfg.mla_kv_rank + cfg.mla_d_rope) * item
    return 0


def _reset_slot_rows(caches, i: int):
    """Zero slot ``i``'s rows of every state leaf that is *not* masked by a
    cache length (recurrent/shift/conv state carries forward unmasked, so a
    released slot's state must not leak into its successor).  Length-masked
    K/V payloads are skipped — their ``length`` rows are zeroed instead and
    the attention mask never reads past it.  Axis rules are relative to the
    trailing dims so they hold under any layer/super-block stacking."""

    def leaf(path, x):
        key = path[-1]
        name = getattr(key, "name", getattr(key, "key", ""))
        if name in ("k", "v", "c", "kr"):
            return x
        if name == "length":
            axis = x.ndim - 1
        elif name in ("wkv", "ssm"):
            axis = x.ndim - 4
        elif name in ("shift", "cm_shift"):
            axis = x.ndim - 2
        elif name == "conv":
            axis = x.ndim - 3
        else:
            raise ValueError(f"unknown cache leaf {name!r}")
        return x.at[(slice(None),) * axis + (i,)].set(0)

    return jax.tree_util.tree_map_with_path(leaf, caches)


class Engine:
    """Slot-based continuous batching over the shared decode path.

    ``recipe`` shards the GSPMD path.  ``mesh`` + ``microbatches`` switch
    prefill and decode to the explicit tensor-parallel step with staggered
    non-blocking collectives
    (:func:`repro.serve.tp_decode.make_tp_decode_step`), with the weights
    and the KV cache committed to its layout.
    """

    def __init__(self, cfg, params, scfg: ServeConfig, recipe=None, *,
                 mesh=None, microbatches: int = 0,
                 featurizer: Callable | None = None):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.recipe = recipe
        B = scfg.batch_slots
        self.state = lm.DecodeState(
            caches=lm.init_cache(cfg, B, scfg.max_len),
            positions=jnp.zeros((B,), jnp.int32),
        )
        self.slots = [_Slot() for _ in range(B)]
        # (request id, prompt, prompt embeds, max new tokens, its open
        # ``engine.queued`` span)
        self.queue: list[tuple[int, list[int], Any, int, TraceAnnotation]] = []
        self.finished: dict[int, list[int]] = {}
        self.ledger = KVLedger(slots=B, max_len=scfg.max_len,
                               bytes_per_pos=_kv_bytes_per_pos(cfg))
        self._key = jax.random.PRNGKey(scfg.seed)
        self._featurize = featurizer or (lambda ids: _np_sinusoidal(ids, cfg.d_model))
        self._embeds_in = cfg.input_kind == "embeds"
        tp = mesh is not None and microbatches
        # one prefill program kind per engine: one row per call into its
        # slot; all slots in one call (the TP program steps every slot); or
        # token by token over all slots (recurrent state, capacity dispatch)
        self._prefill_mode = ("token" if cfg.family not in _CHUNK_FAMILIES
                              else "slots" if tp else "row")

        def gspmd_step(params, state, batch, counts, *, prefill=False, slot=None):
            with use_recipe(self.recipe):
                return lm.decode_step(params, state, batch, cfg,
                                      new_counts=counts, prefill=prefill, slot=slot)

        # the compiled step programs: (params, state, batch, counts) ->
        # (logits, state); the state is donated, so the KV cache is updated
        # in place instead of copied every step.  The single-host prefill
        # takes a fifth argument, the int32 slot of the batch's first row.
        if tp:
            from repro.serve.tp_decode import make_tp_decode_step, place_tp

            step = make_tp_decode_step(cfg, mesh, slots=B, microbatches=microbatches,
                                       attn_impl=cfg.attn_impl)
            # weights and cache live in the TP layout from here on, each
            # device holding its shard; the one TP program runs both the
            # prefill chunks and the decode steps, so neither reshards them
            self.params, self.state = place_tp(cfg, mesh, params, self.state)
            self.prefill_fn = self.decode_fn = jax.jit(step, donate_argnums=1)
        else:
            self.prefill_fn = jax.jit(
                lambda p, s, b, c, slot=None: gspmd_step(p, s, b, c, prefill=True, slot=slot),
                donate_argnums=1)
            self.decode_fn = jax.jit(gspmd_step, donate_argnums=1)

        vocab, temperature = cfg.vocab, scfg.temperature

        def _pick_tokens(logits, key):
            """The next token of every row from a step program's logits: the
            argmax of the last position over the real vocabulary (the padded
            columns are never picked; ties go to the first index) or, at
            ``temperature`` > 0, a draw from its softmax.  Returns the (B,)
            int32 ids and the key for the next draw."""
            last = logits[:, -1, :vocab]
            if temperature > 0:
                key, sub = jax.random.split(key)
                ids = jax.random.categorical(sub, last.astype(jnp.float32) / temperature)
            else:
                ids = jnp.argmax(last, axis=-1)
            return ids.astype(jnp.int32), key

        # a program of its own, launched right behind the decode program, so
        # the host pulls B ids instead of B x vocab logits; its module,
        # ``jit__pick_tokens``, is neither step program's
        self.pick_fn = jax.jit(_pick_tokens)

    # ------------------------------------------------------------ public ----
    def submit(self, request_id: int, prompt: list[int] | None = None,
               max_new_tokens: int = 16, prompt_embeds=None) -> None:
        """Queue a request.  ``prompt`` is a token-id list; embeds-input
        models may instead (or additionally) pass ``prompt_embeds``
        (P, d_model) — token ids are featurized when only ids are given."""
        if prompt is None and prompt_embeds is None:
            raise ValueError("submit needs a prompt and/or prompt_embeds")
        prompt = list(prompt) if prompt is not None else []
        if prompt_embeds is not None:
            prompt_embeds = np.asarray(prompt_embeds, np.float32)
            if prompt_embeds.ndim != 2 or prompt_embeds.shape[1] != self.cfg.d_model:
                raise ValueError(f"prompt_embeds must be (P, {self.cfg.d_model})")
        elif self._embeds_in:
            prompt_embeds = self._featurize(prompt)
        plen = len(prompt_embeds) if prompt_embeds is not None else len(prompt)
        if plen + max_new_tokens > self.scfg.max_len:
            raise ValueError(
                f"request {request_id}: prompt {plen} + {max_new_tokens} new "
                f"exceeds max_len {self.scfg.max_len}"
            )
        queued = TraceAnnotation("engine.queued", rid=request_id)
        queued.__enter__()
        self.queue.append((request_id, prompt, prompt_embeds, max_new_tokens, queued))

    @property
    def in_flight(self) -> dict[int, list[int]]:
        """Partial outputs of requests still resident in slots — what a
        ``run(max_steps)`` that hit its step budget leaves behind."""
        return {s.request_id: list(s.tokens) for s in self.slots
                if s.request_id is not None}

    def run(self, max_steps: int = 10_000) -> dict[int, list[int]]:
        """Drive admission + decode until the queue drains or ``max_steps``
        decode steps have run.  Returns the finished map; anything still
        resident is reported via :attr:`in_flight`."""
        steps = 0
        while (self.queue or self.in_flight) and steps < max_steps:
            self._fill_slots()
            self._decode_once()
            steps += 1
        return self.finished

    # ---------------------------------------------------------- internals ----
    def _fill_slots(self) -> None:
        free = [i for i, slot in enumerate(self.slots) if slot.request_id is None]
        n = min(len(free), len(self.queue))
        if not n:
            return
        newly: list[tuple[int, list[int], Any]] = []
        with TraceAnnotation("engine.admit", admitted=n, queued=len(self.queue) - n,
                             kv_valid_bytes=self.ledger.valid_bytes()):
            for i in free[:n]:
                rid, prompt, embeds, max_new, queued = self.queue.pop(0)
                queued.__exit__(None, None, None)
                plen = len(embeds) if embeds is not None else len(prompt)
                self.ledger.admit(i, plen, max_new)
                slot = self.slots[i]
                slot.request_id = rid
                slot.tokens = list(prompt)
                slot.remaining = max_new
                slot.next_embed = embeds[-1] if embeds is not None else None
                self.state = lm.DecodeState(
                    caches=_reset_slot_rows(self.state.caches, i),
                    positions=self.state.positions.at[i].set(0),
                )
                newly.append((i, prompt, embeds))
        self._prefill(newly)

    # ------------------------------------------------------------ prefill ----
    def _feed(self, feeds, rows: int, S: int):
        """Host arrays of one step call: ``feeds`` [(row, ids or embeds)]
        laid into a (rows, S) token batch (or (rows, S, d_model) embeds), and
        each row's count of them."""
        counts = np.zeros((rows,), np.int32)
        if self._embeds_in:
            buf = np.zeros((rows, S, self.cfg.d_model), np.float32)
        else:
            buf = np.zeros((rows, S), np.int32)
        for r, feed in feeds:
            buf[r, : len(feed)] = feed
            counts[r] = len(feed)
        return {"embeds" if self._embeds_in else "tokens": buf}, counts

    def _prefill(self, newly) -> None:
        """Admission-time prefill of the newly filled slots, each prompt
        less its last token (the first decode step feeds that one).

        Chunk-capable families run each prompt as one ``prefill=True``
        chunk padded to a power-of-two bucket.  The single-host engine
        issues one one-row program call per admitted slot, back to back:
        each writes that slot's cache rows, length and position alone, and
        the resident slots are neither read nor computed.  The TP program
        steps every slot, so there one call carries all the prompts and
        per-slot ``new_counts`` keep the resident rows' K/V untouched.
        Recurrent/moe families step token by token over all slots under the
        same masking."""
        with TraceAnnotation("engine.prefill_launch") as span:
            B = self.scfg.batch_slots
            feeds = []  # (slot, ids[:-1] or embeds[:-1])
            for i, prompt, embeds in newly:
                feed = embeds[:-1] if embeds is not None else prompt[:-1]
                if len(feed):
                    feeds.append((i, feed))
            if not feeds:
                return
            fed = sum(len(f) for _, f in feeds)
            S = max(len(f) for _, f in feeds)
            if self._prefill_mode == "token":
                calls = [self._feed([(i, feed[t:t + 1]) for i, feed in feeds if t < len(feed)], B, 1)
                         for t in range(S)]
                bucket, rows = 1, B
            else:
                bucket = S = min(self.scfg.max_len, 1 << (S - 1).bit_length())  # fewer recompiles
                if self._prefill_mode == "slots":
                    calls, rows = [self._feed(feeds, B, S)], B
                else:
                    calls = [self._feed([(0, feed)], 1, S) + (np.int32(i),) for i, feed in feeds]
                    rows = 1
            span.set_metadata(rows=len(feeds), bucket=bucket, calls=len(calls),
                              padded_tokens=len(calls) * rows * bucket - fed)
            # every call's inputs go to the device before the first call,
            # so the calls follow each other with nothing between them
            for args in jax.device_put(calls):
                _, self.state = self.prefill_fn(self.params, self.state, *args)
            for i, feed in feeds:
                self.ledger.advance(i, len(feed))

    # ------------------------------------------------------------- decode ----
    def _decode_once(self) -> None:
        B = self.scfg.batch_slots
        with TraceAnnotation("engine.decode_launch") as span:
            feeds = []
            for i, slot in enumerate(self.slots):
                if slot.request_id is None:
                    continue
                if not self._embeds_in:
                    feeds.append((i, [slot.tokens[-1]]))
                elif slot.next_embed is not None:
                    feeds.append((i, [slot.next_embed]))
                else:
                    feeds.append((i, self._featurize([slot.tokens[-1]])))
            rows = len(feeds)
            span.set_metadata(rows=rows)
            batch, counts = jax.device_put(self._feed(feeds, B, 1))
            logits, self.state = self.decode_fn(self.params, self.state, batch, counts)
        with TraceAnnotation("engine.fetch") as span:
            ids, self._key = self.pick_fn(logits, self._key)
            ids = np.asarray(ids)
            span.set_metadata(bytes=ids.nbytes)
        with TraceAnnotation("engine.sample", rows=rows):
            for i, slot in enumerate(self.slots):
                if slot.request_id is None:
                    continue
                self.ledger.advance(i, 1)
                nxt = int(ids[i])
                slot.tokens.append(nxt)
                if self._embeds_in:
                    slot.next_embed = self._featurize([nxt])[0]
                slot.remaining -= 1
                if nxt == self.scfg.eos_token or slot.remaining <= 0:
                    self.finished[slot.request_id] = slot.tokens
                    self.ledger.release(i)
                    self.slots[i] = _Slot()
