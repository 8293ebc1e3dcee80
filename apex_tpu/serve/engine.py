"""The serve engine: jitted prefill/decode steps with a donated cache,
driven by the continuous-batching scheduler.

Shape discipline — the engine compiles at most THREE programs and
reuses them for the whole serving lifetime (replay after a preemption
goes through the same decode program; that reuse IS the bit-exactness
argument below):

- the **prefill step** runs one sequence at the static padded prompt
  length (``max_prompt_len``) or, in an engine built with
  ``prefill_chunk`` under its ``max_prompt_len``, ``prefill_chunk`` tokens
  of one sequence's prompt from a start offset: a long prompt goes in a
  chunk a round, at most one chunk a round, beside that round's decode
  batch, and only its LAST chunk samples a token (the model carries what
  a chunk leaves to the next: K|V pages, recurrent state). An engine
  without ``prefill_chunk`` compiles and dispatches what it always has;
- the **decode step** runs the full fixed-capacity batch
  (``max_batch`` slots, inactive slots masked to the null page). With
  ``spec_k > 0`` the SAME compiled decode program doubles as the
  speculative **verifier**: rows ``0..k`` carry ``k+1`` consecutive
  positions of ONE sequence (the last committed token plus the draft
  tokens) — legal because every row's K/V writes land before any row
  attends and per-row ``seq_lens`` mask causality;
- the **draft-decode step** (``spec_k > 0`` only) is the decode
  program compiled for the depth-truncated draft model over its own
  page pool (:mod:`apex_tpu.serve.spec`).

Fixed shapes are not just a compile-cache nicety: because no operation
in the forward mixes batch rows, a slot's row is a function of that
slot's inputs alone, independent of batch company — so replaying a
preempted sequence's generated tokens through the SAME decode program
reproduces its cache and logits BIT-exactly (asserted in
``tests/test_serve.py``), and speculative greedy output is
token-identical to plain paged decode (``tests/test_serve_spec.py``).
The cache pytrees are donated through all steps: the pools update in
place, never 2x resident.

fp8 weight-streaming (``fp8_weights=True``): the block linear kernels
quantize ONCE at engine build to e4m3 with per-tensor scales
(:func:`apex_tpu.serve.model.quantize_gpt_weights`), cutting the
weight bytes every decode step streams ~2x vs bf16; the forward reads
them through the fused dequant-matmul (``ops.fp8_matmul``). Orthogonal
to and composable with speculative decoding.

One round ahead of the tokens read: nothing the host decides between
two rounds depends on a token's VALUE (a sequence ends by count, pages
grow by position, admission goes by slots and pages), so sampled tokens
stay on the device — ``last_tok[max_batch]``, which the decode program
reads its input from and returns with the round's argmaxes merged in,
and in which the prefill program sets the prompt's first token — and
``step()`` N dispatches its prefills and its decode BEFORE it reads
round N-1's tokens (``copy_to_host_async`` at dispatch, the wait one
round late). ``seq.tokens``, ``tokens_generated``, ``done`` and TTFT
advance only when a value is on the host; scheduling goes by
``Sequence.num_dispatched``. Where a value IS needed the engine drains
first (reads what is in flight early, nothing else): preemption and
replay, the speculative rounds, ``preempt()``. A step that sends out
no decode round reads everything itself (``_due``); ``run()`` and the
end of work leave nothing in flight. Same programs' arithmetic, same tokens,
bit for bit (``tests/test_serve.py``).

Tensor parallelism: with a model-parallel mesh installed
(``parallel_state.initialize_model_parallel(tp)``), both steps wrap in
``shard_map`` with layouts from :mod:`apex_tpu.serve.rules` — the FULL
(tp=1-layout) param tree and cache are split by the in_specs, the TP
layers run their training collectives, and logits/next-token outputs
come back replicated. The host-side scheduler is unchanged.

Telemetry (``docs/serve.md`` / ``docs/observability.md``): with a
recorder attached, every request gets a span trace — queue-wait →
prefill (→ decode-replay on resume) → per-token decode — through
:mod:`apex_tpu.monitor.spans`, token latency / TTFT / queue wait feed
O(1)-memory streaming histograms, and each scheduler round records
pool-occupancy + queue-depth gauges inside a per-step record (so the
:class:`~apex_tpu.monitor.health.Watchdog`'s serve detectors observe
them online). All host-clock, zero jax in the hot path: the compiled
decode/prefill programs are byte-identical spans-on vs spans-off
(asserted in ``tests/test_serve_telemetry.py``), and detached mode
costs one global read per hook.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import _compat
from apex_tpu._compat import shard_map
from apex_tpu.models.gpt import GPTConfig
from apex_tpu.monitor import _state as _monitor_state
from apex_tpu.monitor import flight as _mflight
from apex_tpu.monitor import hooks as _mhooks
from apex_tpu.monitor import spans as _mspans
from apex_tpu.serve import cache as cache_mod
from apex_tpu.serve import model as model_mod
from apex_tpu.serve import rules as rules_mod
from apex_tpu.serve import spec as spec_mod
from apex_tpu.serve.scheduler import RUNNING, Scheduler, Sequence
from apex_tpu.transformer import parallel_state as ps


def _default_impls():
    on_tpu = _compat.on_tpu()
    return (("kernel" if on_tpu else "reference"),
            ("flash" if on_tpu else "reference"))


@dataclasses.dataclass
class _InFlight:
    """What one dispatch left on the device for the host to read later:
    a prefill's first token (one row) or a decode round's tokens."""

    step: int                          # the step() that dispatched it
    rows: List[Tuple[Sequence, int]]   # (sequence, batch row it took)
    toks: Any                          # ``last_tok`` after the dispatch
    logits: Any                        # under ``record_logits``, else None
    aux: Any                           # the model's own outputs, or None
    decode: bool
    t_dispatch: float


class ServeEngine:
    """Paged-cache serving on one host (optionally TP-sharded).

    ``cfg`` is a ``models.gpt.GPTConfig`` with ``params`` the full (tp=1
    layout) ``models.gpt.GPT`` parameter tree (``variables["params"]``),
    or any object that answers the model interface
    (:class:`apex_tpu.serve.model.GPTServed` spells it out) with that
    model's tree: the engine, the scheduler and the page allocator know
    nothing of the model but that interface. Sampling is greedy argmax —
    deterministic by design, which the preempt/resume bit-exactness
    contract relies on.
    """

    def __init__(self, cfg, params, *, num_pages: int,
                 max_seq_len: int, max_prompt_len: int,
                 page_size: Optional[int] = None, max_batch: int = 4,
                 fp8_kv: bool = False, fp8_margin: float = 2.0,
                 paged_impl: Optional[str] = None,
                 attention_impl: Optional[str] = None,
                 autotune: Optional[str] = None,
                 record_logits: bool = False,
                 interpret: Optional[bool] = None,
                 spec_k: int = 0,
                 draft_num_layers: Optional[int] = None,
                 draft_cfg: Optional[GPTConfig] = None,
                 draft_params=None,
                 fp8_weights: bool = False,
                 fp8_weight_margin: float = 0.0,
                 prefill_chunk: Optional[int] = None):
        d_impl, p_impl = _default_impls()
        self.model = model = model_mod.as_served(cfg)
        self.cfg = cfg = model.cfg
        self.tp = ps.get_tensor_model_parallel_world_size()
        # a prompt that fits one chunk is one prefill: the programs of an
        # engine that was never asked to chunk
        self.prefill_chunk = (int(prefill_chunk) if prefill_chunk
                              and prefill_chunk < max_prompt_len else None)
        model.check(tp=self.tp, fp8_kv=fp8_kv, fp8_weights=fp8_weights,
                    spec_k=spec_k, prefill_chunk=self.prefill_chunk or 0)
        self.fp8_weights = bool(fp8_weights)
        if fp8_weights:
            # one-time e4m3 encode of the block linear kernels: same
            # tree shape (+ scalar scale leaves), so the TP rules and
            # shard_map specs below apply unchanged
            params = model.quantize_weights(params, margin=fp8_weight_margin)
        self.params = params
        self.paged_impl = paged_impl or d_impl
        self.attention_impl = attention_impl or p_impl
        self.interpret = interpret
        self.autotune = autotune
        psize = cache_mod.resolve_page_size(
            **model.page_geometry(self.tp), context_len=max_seq_len,
            fp8=fp8_kv, batch=max_batch, page_size=page_size,
            autotune=autotune)
        if max_seq_len > model.max_seq_len:
            raise ValueError(f"max_seq_len {max_seq_len} exceeds the "
                             f"model's {model.max_seq_len}")
        if max_prompt_len > max_seq_len:
            raise ValueError("max_prompt_len exceeds max_seq_len")
        self.max_seq_len = max_seq_len
        self.max_prompt_len = max_prompt_len
        self.pages_per_seq = -(-max_seq_len // psize)
        if self.prefill_chunk and self.prefill_chunk % psize:
            raise ValueError(f"prefill_chunk {prefill_chunk} must be whole "
                             f"pages of {psize}")
        self.ccfg = model.cache_config(num_pages=num_pages, page_size=psize,
                                       fp8=fp8_kv, fp8_margin=fp8_margin,
                                       max_batch=max_batch)
        self.state = cache_mod.init_cache(self.ccfg)
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if self.spec_k:
            if self.spec_k + 1 > max_batch:
                raise ValueError(
                    f"spec_k={spec_k} needs max_batch >= {spec_k + 1} "
                    f"(the verify window rides the decode batch rows), "
                    f"got max_batch={max_batch}")
            if fp8_kv:
                # the fp8-KV slot-0 scale rule is sequential: a verify
                # window crossing a page boundary would scatter the old
                # and the fresh page scale to the SAME pool index in
                # one call (undefined order) and quantize later rows
                # with the stale scale — bit-parity with plain decode
                # would silently break
                raise ValueError("spec_k > 0 does not compose with "
                                 "fp8_kv (per-page slot-0 scales need "
                                 "sequential writes)")
            if draft_params is None:
                layers = draft_num_layers or max(1, cfg.num_layers // 2)
                self.draft_model, self.draft_params = model.derive_draft(
                    self.params, num_layers=layers)
            else:
                if draft_cfg is None:
                    raise ValueError("draft_params requires draft_cfg")
                self.draft_model = model_mod.as_served(draft_cfg)
                self.draft_params = (
                    self.draft_model.quantize_weights(
                        draft_params, margin=fp8_weight_margin)
                    if fp8_weights else draft_params)
            self.draft_cfg = self.draft_model.cfg
            try:
                self.draft_model.check(tp=self.tp)
            except ValueError as e:
                raise ValueError(f"draft {e}") from None
            # the draft pool mirrors the target pool's geometry
            # (num_pages, page_size) so the draft REUSES each
            # sequence's block table — zero new allocator state
            self.draft_ccfg = self.draft_model.cache_config(
                num_pages=num_pages, page_size=psize)
            self.draft_state = cache_mod.init_cache(self.draft_ccfg)
        #: entries of the block table a chunk is given: the pages of the
        #: longest prompt's chunks, which bound the context a chunk attends
        self.prefill_pages = (
            -(-max_prompt_len // self.prefill_chunk) * self.prefill_chunk
            // psize if self.prefill_chunk else self.pages_per_seq)
        self.sched = Scheduler(num_pages=num_pages, page_size=psize,
                               max_batch=max_batch,
                               lookahead=self.spec_k,
                               prefill_chunk=self.prefill_chunk)
        self.max_batch = max_batch
        self.slots: List[Optional[Sequence]] = [None] * max_batch
        self.record_logits = record_logits
        self.logits_log: Dict[int, Dict[int, np.ndarray]] = {}
        self.aux_log: Dict[int, Dict[int, dict]] = {}
        # one entry a decode round: the round's period as the consumer
        # of its tokens sees it (``_fetch``)
        self.decode_step_times: List[float] = []
        self.tokens_generated = 0
        # the engine runs one round ahead of the tokens it has read: the
        # dispatches whose tokens are still on the device, oldest first
        self._in_flight: List[_InFlight] = []
        self._step_no = 0
        self._t_tokens = 0.0           # a decode round's tokens last arrived
        self._next_id = 0
        self.seqs: Dict[int, Sequence] = {}    # every request ever added
        self._build_steps()

    # -- jitted steps ------------------------------------------------

    def _build_steps(self):
        model, ccfg = self.model, self.ccfg

        # ``aux`` (the model's own small outputs, {} for GPT) leaves the
        # program beside the logits: no leaf, no output, same program.
        # Sampled tokens are fed back ON THE DEVICE: ``last_tok`` [B] is
        # the token each batch row feeds next. The decode program reads
        # its input from it and returns it with the active rows' argmaxes
        # merged in; the prefill program sets the prompt's first token at
        # the sequence's row. The host reads a round's tokens a round late.
        def decode(params, state, bt, pos, last_tok, act):
            logits, state, aux = model.decode(
                ccfg, params, state, bt, pos, jnp.where(act, last_tok, 0),
                act, paged_impl=self.paged_impl, interpret=self.interpret,
                autotune=self.autotune)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return logits, jnp.where(act, nxt, last_tok), state, aux

        def prefill(params, state, bt, length, ids, last_tok, slot, *start):
            # ``start``: an engine with ``prefill_chunk`` passes the chunk's
            # offset; a chunk before the last then leaves its row's
            # ``last_tok`` a token nobody reads (the row decodes only after
            # the last chunk has set it)
            logits, state, aux = model.prefill(
                ccfg, params, state, bt, length, ids, slot=slot,
                attention_impl=self.attention_impl,
                interpret=self.interpret, autotune=self.autotune,
                **dict(zip(("start",), start)))
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return logits, last_tok.at[slot].set(nxt), state, aux

        draft = None
        if self.spec_k:
            dmodel, dccfg = self.draft_model, self.draft_ccfg

            def draft(params, state, bt, pos, tok, act):
                # greedy draft: only the argmaxes leave the program
                logits, state, _ = dmodel.decode(
                    dccfg, params, state, bt, pos, tok, act,
                    paged_impl=self.paged_impl, interpret=self.interpret,
                    autotune=self.autotune)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), state

        self._tok_sharding = None      # where ``last_tok`` lives
        if self.tp > 1:
            mesh = ps.get_mesh()
            from jax.sharding import NamedSharding, PartitionSpec as P
            pspec = rules_mod.match_serve_rules(
                model.param_rules, self.params, world=self.tp)
            cspec = rules_mod.match_serve_rules(
                model.cache_rules, self.state, world=self.tp)

            def place(tree, spec):
                # once, in the layout the step programs' in_specs name:
                # left where the caller had them (one device), the
                # weights would be re-scattered over the mesh by every
                # prefill and decode call
                return jax.device_put(tree, jax.tree.map(
                    lambda sp: NamedSharding(mesh, sp), spec,
                    is_leaf=lambda x: isinstance(x, P)))

            self.params = place(self.params, pspec)
            self.state = place(self.state, cspec)
            self._tok_sharding = NamedSharding(mesh, P())
            decode = shard_map(
                decode, mesh=mesh,
                in_specs=(pspec, cspec, P(), P(), P(), P()),
                out_specs=(P(), P(), cspec, P()), check_vma=False)
            prefill = shard_map(
                prefill, mesh=mesh,
                in_specs=(pspec, cspec) + (P(),) * (
                    6 if self.prefill_chunk else 5),
                out_specs=(P(), P(), cspec, P()), check_vma=False)
            if draft is not None:
                dpspec = rules_mod.match_serve_rules(
                    dmodel.param_rules, self.draft_params, world=self.tp)
                dcspec = rules_mod.match_serve_rules(
                    dmodel.cache_rules, self.draft_state, world=self.tp)
                self.draft_params = place(self.draft_params, dpspec)
                self.draft_state = place(self.draft_state, dcspec)
                draft = shard_map(
                    draft, mesh=mesh,
                    in_specs=(dpspec, dcspec, P(), P(), P(), P()),
                    out_specs=(P(), dcspec), check_vma=False)
        # the cache pytree (arg 1) is donated: the pool mutates in
        # place across steps, never two copies resident (APX007's
        # convention for state threaded through a hot loop)
        self._decode = jax.jit(decode, donate_argnums=(1,))
        self._prefill = jax.jit(prefill, donate_argnums=(1,))
        self._draft_decode = (jax.jit(draft, donate_argnums=(1,))
                              if draft is not None else None)
        self._last_tok = jax.device_put(
            np.zeros((self.max_batch,), np.int32), self._tok_sharding)

    # -- request intake ----------------------------------------------

    def add_request(self, prompt: List[int], max_new_tokens: int) -> int:
        if len(prompt) > self.max_prompt_len:
            raise ValueError(f"prompt length {len(prompt)} exceeds "
                             f"max_prompt_len {self.max_prompt_len}")
        if len(prompt) + max_new_tokens > self.max_seq_len:
            raise ValueError("prompt + max_new_tokens exceeds max_seq_len")
        seq = Sequence(seq_id=self._next_id, prompt=list(prompt),
                       max_new_tokens=max_new_tokens)
        self._next_id += 1
        self.seqs[seq.seq_id] = seq
        # the request ROOT span: opened before the scheduler sees the
        # sequence so the initial queue-wait span parents under it;
        # closed when the last token samples (or never, if the caller
        # abandons the engine — spans are host state, nothing leaks
        # into compiled programs)
        seq.span = _mspans.start("serve/request", seq_id=seq.seq_id,
                                 prompt_tokens=len(seq.prompt),
                                 max_new_tokens=max_new_tokens)
        self.sched.add(seq)
        return seq.seq_id

    # -- host-side step driving --------------------------------------

    def _bt_row(self, seq: Sequence) -> np.ndarray:
        row = np.zeros((self.pages_per_seq,), np.int32)
        row[:len(seq.pages)] = seq.pages
        return row

    def _record(self, seq: Sequence, pos: int, logits_row, aux=None,
                row=None) -> None:
        """Under ``record_logits``: the logits that predict position
        ``pos`` and, where the model gives per-row ``aux``, its values
        for that row (``row`` = the batch row; None = a prefill's one)."""
        if not self.record_logits:
            return
        self.logits_log.setdefault(seq.seq_id, {})[pos] = \
            np.asarray(logits_row)
        if aux and aux.get("rows"):
            self.aux_log.setdefault(seq.seq_id, {})[pos] = {
                k: np.asarray(v if row is None else v[row])
                for k, v in aux["rows"].items()}

    # -- one round ahead of the tokens read ---------------------------

    def _hold(self, rows, logits, aux, *, decode: bool,
              t_dispatch: float) -> None:
        """A dispatch's tokens stay on the device (``self._last_tok``
        feeds the next round there). Start their copy to the host
        without waiting for it, with whatever else a reader wants of the
        dispatch, and count a token in flight for each row."""
        held = {}
        if aux and self.record_logits and aux.get("rows"):
            held["rows"] = aux["rows"]
        if aux and decode and aux.get("round") and _mhooks.enabled():
            # the model's own counters of a decode round
            held["round"] = aux["round"]
        e = _InFlight(self._step_no, rows, self._last_tok,
                      logits if self.record_logits else None, held,
                      decode, t_dispatch)
        for a in jax.tree.leaves((e.toks, e.logits, e.aux)):
            a.copy_to_host_async()
        for seq, _ in rows:
            seq.in_flight += 1
        self._in_flight.append(e)

    def _fetch(self, e: _InFlight):
        """Wait until one dispatch's tokens are on the host. A decode
        round's ``decode_step_times`` entry is its period as the reader
        sees it: from the previous round's tokens arriving (or this
        round's dispatch, whichever is later) to its own arriving, so
        overlapped time counts once and the entry never falls to ~0."""
        toks = np.asarray(e.toks)
        dt = None
        if e.decode:
            now = time.perf_counter()
            dt = now - max(self._t_tokens, e.t_dispatch)
            self._t_tokens = now
            self.decode_step_times.append(dt)
        logits = None if e.logits is None else np.asarray(e.logits)
        return e, toks, logits, jax.device_get(e.aux), dt

    def _commit(self, e: _InFlight, toks, logits, aux, dt) -> None:
        """Count one dispatch's tokens, now that their values are here:
        only this advances ``seq.tokens``, ``tokens_generated``, TTFT
        and ``done``."""
        if e.decode:
            if "round" in aux:
                self.model.record_round(aux["round"])
            if _mhooks.enabled():
                # per-TOKEN latency: each row of the round produced one
                # token — the streaming-percentile source of the serve
                # SLO numbers (p50/p95/p99); one record a dispatch,
                # weighted by its rows
                _mhooks.observe("serve/token_latency_ms", 1e3 * dt,
                                n=len(e.rows))
                _mhooks.gauge("serve/batch_fill",
                              len(e.rows) / self.max_batch)
        for seq, row in e.rows:
            seq.in_flight -= 1
            if logits is not None:
                self._record(seq, seq.num_tokens,
                             logits[row] if e.decode else logits, aux,
                             row if e.decode else None)
            self._sample(seq, toks[row])
        # one event a dispatch, not a token
        _mhooks.counter("serve/tokens_generated", len(e.rows))

    def _take(self, n: int) -> list:
        """Fetch the ``n`` oldest dispatches in flight. With something
        to read, ``serve/token_wait`` covers it: from "the host has
        nothing else to do" to "the values are here", wherever a step
        waits for the device (under ``serve/decode_step``, under
        ``serve/sample`` in a step that sent out no decode, in
        ``_drain``)."""
        batch = self._in_flight[:n]
        del self._in_flight[:n]
        if not batch:
            return []
        with _mspans.span("serve/token_wait", n_read=len(batch)):
            return [self._fetch(e) for e in batch]

    def _drain(self, reason: str) -> None:
        """Read everything in flight NOW, because a token's value is
        needed (``preempt``, ``replay``, ``spec``). Reading early is all
        it is."""
        if not self._in_flight:
            return
        _mhooks.counter("serve/pipeline_drains", reason=reason)
        for f in self._take(len(self._in_flight)):
            self._commit(*f)

    def _free_slot(self, seq: Sequence) -> None:
        for i, s in enumerate(self.slots):
            if s is seq:
                self.slots[i] = None

    def _sample(self, seq: Sequence, token: int) -> None:
        seq.tokens.append(int(token))
        self.tokens_generated += 1
        if seq.num_generated == 1 and seq.ttft_ms is None \
                and seq.arrival_t:
            # time-to-first-token, measured ONCE per request (a resumed
            # sequence replays deterministically — its first token
            # already happened)
            seq.ttft_ms = 1e3 * (time.perf_counter() - seq.arrival_t)
            _mhooks.observe("serve/ttft_ms", seq.ttft_ms)
        if seq.done:
            self.sched.finish(seq)
            self._free_slot(seq)
            _mspans.end(seq.span, seq_id=seq.seq_id,
                        prompt_tokens=len(seq.prompt),
                        new_tokens=seq.num_generated,
                        preemptions=seq.n_preemptions,
                        ttft_ms=round(seq.ttft_ms, 3)
                        if seq.ttft_ms is not None else None,
                        queue_wait_ms=round(1e3 * seq.queue_wait_s, 3))
            seq.span = None

    def _replay_generated(self, seq: Sequence) -> None:
        """Recompute the cache for a resumed sequence's generated
        tokens through the decode program (single-slot-active batches):
        the same compiled rows as the original steps, hence bit-exact.
        The last token is NOT replayed — it is the next decode's
        input. The fed tokens are VALUES (``seq.tokens``), so the caller
        has drained: the host holds every row's next token, and the
        device's ``last_tok`` is set from them at the end."""
        slot = self.slots.index(seq)
        for j in range(len(seq.prompt), seq.num_tokens - 1):
            tok = np.zeros((self.max_batch,), np.int32)
            pos = np.zeros((self.max_batch,), np.int32)
            act = np.zeros((self.max_batch,), bool)
            bts = np.zeros((self.max_batch, self.pages_per_seq), np.int32)
            tok[slot] = seq.tokens[j]
            pos[slot] = j
            act[slot] = True
            bts[slot] = self._bt_row(seq)
            logits, _, self.state, aux = self._decode(
                self.params, self.state, jnp.asarray(bts),
                jnp.asarray(pos), jnp.asarray(tok), jnp.asarray(act))
            self._record(seq, j + 1, logits[slot], aux, slot)
            seq.num_cached = j + 1
        tok = np.zeros((self.max_batch,), np.int32)
        for i, s in enumerate(self.slots):
            if s is not None:
                tok[i] = s.tokens[-1]
        self._last_tok = jax.device_put(tok, self._tok_sharding)

    # -- speculative decoding ----------------------------------------

    def _blank_batch(self):
        return (np.zeros((self.max_batch,), np.int32),
                np.zeros((self.max_batch,), np.int32),
                np.zeros((self.max_batch,), bool),
                np.zeros((self.max_batch, self.pages_per_seq), np.int32))

    def _draft_propose(self, seq: Sequence, bt: np.ndarray,
                       k: int) -> List[int]:
        """Draft ``k`` tokens for one sequence. First ingests the
        not-yet-drafted committed positions ``draft_cached..n-1``
        through the draft-decode program — up to ``max_batch``
        CONSECUTIVE POSITIONS of this one sequence per call (legal for
        the same reason verify is: writes land before reads, per-row
        ``seq_lens`` mask causality) — which both rebuilds the draft
        cache over any rejected-round garbage and, via the last live
        row (the feed of ``tokens[n-1]``), yields the first proposal.
        Then ``k-1`` single-row calls extend speculatively."""
        n = seq.num_tokens
        d1 = None
        for lo in range(seq.draft_cached, n, self.max_batch):
            hi = min(lo + self.max_batch, n)
            tok, pos, act, bts = self._blank_batch()
            cnt = hi - lo
            tok[:cnt] = seq.tokens[lo:hi]
            pos[:cnt] = np.arange(lo, hi, dtype=np.int32)
            act[:cnt] = True
            bts[:cnt] = bt
            nxt, self.draft_state = self._draft_decode(
                self.draft_params, self.draft_state, jnp.asarray(bts),
                jnp.asarray(pos), jnp.asarray(tok), jnp.asarray(act))
            if hi == n:
                d1 = int(np.asarray(nxt)[cnt - 1])
        seq.draft_cached = n
        draft = [d1]
        for j in range(1, k):
            tok, pos, act, bts = self._blank_batch()
            tok[0] = draft[-1]
            pos[0] = n - 1 + j
            act[0] = True
            bts[0] = bt
            nxt, self.draft_state = self._draft_decode(
                self.draft_params, self.draft_state, jnp.asarray(bts),
                jnp.asarray(pos), jnp.asarray(tok), jnp.asarray(act))
            draft.append(int(np.asarray(nxt)[0]))
        return draft

    def _spec_round(self, seq: Sequence) -> None:
        """One speculative round for one sequence: draft ``k`` tokens,
        verify all ``k+1`` positions in ONE call of the compiled decode
        program (rows 0..k = positions ``n-1..n-1+k``; row 0 feeds the
        last committed token, rows 1..k the draft), then commit the
        longest accepted prefix + the verifier's bonus token
        (:func:`apex_tpu.serve.spec.accept_greedy`) — at least one
        token per round, token-identical to plain greedy decode.
        Rejected-suffix K/V in both pools is overwritten by the next
        round's window before any row can attend to it (rows only read
        positions <= their own)."""
        n = seq.num_tokens
        remaining = seq.max_new_tokens - seq.num_generated
        k = min(self.spec_k, remaining - 1)
        bt = self._bt_row(seq)
        draft: List[int] = []
        if k > 0:
            with _mspans.span("serve/draft", parent=seq.span,
                              seq_id=seq.seq_id, k=k):
                draft = self._draft_propose(seq, bt, k)
        tok, pos, act, bts = self._blank_batch()
        tok[0] = seq.tokens[-1]
        if k > 0:
            tok[1:k + 1] = draft
        pos[:k + 1] = (n - 1) + np.arange(k + 1, dtype=np.int32)
        act[:k + 1] = True
        bts[:k + 1] = bt
        t0 = time.perf_counter()
        with _mspans.span("serve/verify", parent=seq.span,
                          seq_id=seq.seq_id, rows=k + 1):
            logits, next_toks, self.state, aux = self._decode(
                self.params, self.state, jnp.asarray(bts),
                jnp.asarray(pos), jnp.asarray(tok), jnp.asarray(act))
            next_np = np.asarray(next_toks)
        logits_np = np.asarray(logits) if self.record_logits else None
        dt = time.perf_counter() - t0
        self.decode_step_times.append(dt)
        committed, m = spec_mod.accept_greedy(
            draft, [int(t) for t in next_np[:k + 1]])
        # the target cache is now valid through position n-1+m (the
        # committed window rows); the draft cache through n-1+min(m,
        # k-1) — position n-1+j holds d_j's K/V, and d_k was never fed
        seq.num_cached = n + m
        if k > 0:
            seq.draft_cached = n + min(m, k - 1)
        _mhooks.counter("serve/spec_rounds")
        if k > 0:
            _mhooks.counter("serve/spec_draft_tokens", k)
            _mhooks.counter("serve/spec_accepted_tokens", m)
            _mhooks.observe("serve/spec_accept_rate", m / k)
        for i, t in enumerate(committed):
            if logits_np is not None:
                self._record(seq, n + i, logits_np[i], aux, i)
            self._sample(seq, t)
        _mhooks.counter("serve/tokens_generated", len(committed))
        if _mhooks.enabled():
            _mhooks.observe("serve/token_latency_ms",
                            1e3 * dt / len(committed), n=len(committed))
            _mhooks.gauge("serve/batch_fill",
                          (k + 1) / self.max_batch)

    def _do_prefill(self, seq: Sequence) -> None:
        """The sequence's whole prompt or, chunked, its next chunk (from
        ``seq.num_cached``). The first takes the batch row; the last (the
        only one, unchunked) leaves the prompt's first token in flight or,
        resumed, replays the generated tokens."""
        chunk = self.prefill_chunk
        start = seq.num_cached if chunk else 0
        n = min(chunk or len(seq.prompt), len(seq.prompt) - start)
        last = start + n == len(seq.prompt)
        resumed = seq.num_generated > 0
        if resumed and last:
            self._drain("replay")
        if start == 0:
            seq.slot = self.slots.index(None)
            self.slots[seq.slot] = seq
        slot = seq.slot
        ids = np.zeros((chunk or self.max_prompt_len,), np.int32)
        ids[:n] = seq.prompt[start:start + n]
        bt = self._bt_row(seq)
        more, where = (), {}
        if chunk:
            # the null page past the sequence's own
            bt = np.pad(bt, (0, max(0, self.prefill_pages - len(bt)))
                        )[:self.prefill_pages]
            more = (np.int32(start),)
            where = dict(start=start, n_tokens=n, last=last)
        # the span closes at DISPATCH: the prompt's first token stays on
        # the device (row ``slot`` of ``last_tok``) and is counted when a
        # later step reads it. A child of the round by nesting;
        # ``seq_id`` links it to the request.
        with _mspans.span("serve/prefill", seq_id=seq.seq_id,
                          resumed=resumed,
                          prompt_tokens=len(seq.prompt), **where):
            t0 = time.perf_counter()
            logits, self._last_tok, self.state, aux = self._prefill(
                self.params, self.state, jnp.asarray(bt), np.int32(n),
                jnp.asarray(ids), self._last_tok, np.int32(slot), *more)
            seq.num_cached = start + n
        if chunk:
            _mhooks.counter("serve/prefill_chunks")
            if self.record_logits and aux and aux.get("rows"):
                # a chunk's rows, by its start (the last chunk's are kept
                # with its logits too)
                self.aux_log.setdefault(seq.seq_id, {})["chunk", start] = {
                    k: np.asarray(v) for k, v in aux["rows"].items()}
            if not last:
                return
        _mhooks.counter("serve/prefills")
        if not resumed:
            self._hold([(seq, slot)], logits, aux, decode=False,
                       t_dispatch=t0)
        else:
            # resumed: the generated tokens already exist; rebuild the
            # cache deterministically instead of re-sampling
            self._record(seq, len(seq.prompt), logits, aux)
            with _mspans.span("serve/replay", parent=seq.span,
                              seq_id=seq.seq_id,
                              tokens=max(0, seq.num_generated - 1)):
                self._replay_generated(seq)

    def step(self) -> bool:
        """One scheduler round: prefills + one batched decode. Returns
        whether any work remains. With a recorder attached the round
        runs inside one per-step record (gauges/counters below land on
        it, so the Watchdog's serve detectors see them online)."""
        rec = _monitor_state.recorder
        if rec is not None and rec._open_step is None:
            with rec.step():
                return self._step_inner()
        return self._step_inner()

    def _step_inner(self) -> bool:
        """One round, spanned by phase (docs/observability.md has the
        table): ``serve/round`` holds ``serve/schedule``, one
        ``serve/prefill`` per admitted sequence, ``serve/decode_inputs``,
        ``serve/decode_step``, ``serve/sample`` and ``serve/gauges``.
        The round DISPATCHES its prefills and its decode, and only then
        reads the tokens the previous round left in flight
        (``_decode_round``): while the host samples them, lets the
        caller admit, schedules and builds the next inputs, the device
        runs this round. Detached, each span is one global read."""
        self._step_no += 1
        with _mspans.span("serve/round"):
            with _mspans.span("serve/schedule"):
                plan = self.sched.schedule()
                if plan.preempted:
                    # a preempted sequence re-enters by its tokens' values
                    self._drain("preempt")
                for seq in plan.preempted:
                    self._free_slot(seq)
            for seq in plan.prefill:
                self._do_prefill(seq)
            decodes = [s for s in plan.decode if s.state == RUNNING]
            if decodes and self.spec_k:
                # speculative mode: one draft+verify round per sequence
                # (the verify window owns the batch rows), accepted by
                # VALUE: synchronous as it always was
                self._drain("spec")
                for seq in decodes:
                    if seq.done or seq.state != RUNNING:
                        continue
                    self._spec_round(seq)
            else:
                self._decode_round(decodes)
            with _mspans.span("serve/gauges"):
                self._record_step_gauges()
        return self.sched.has_work

    def _due(self, decoding: bool) -> int:
        """How many of the dispatches in flight this step reads. With a
        decode round just dispatched (``decoding``) the device has work
        to do while the host reads and counts: the step reads what
        EARLIER steps left. Otherwise it reads everything, because no
        read could hide behind device work: no decode went out (a cold
        start, or a burst's first step: the next round waits for these
        prefills anyway, and a first wave of them would otherwise be
        left queued behind the caller's back), or no sequence is left
        that a next step could dispatch for (the end of the work)."""
        if decoding and (self.sched.waiting or
                         not all(s.sent for s in self.sched.running)):
            return sum(e.step < self._step_no for e in self._in_flight)
        if self._in_flight:
            # this read empties the pipeline: it overlaps nothing
            _mhooks.counter("serve/pipeline_drains", reason="idle")
        return len(self._in_flight)

    def _decode_round(self, decodes: List[Sequence]) -> None:
        """Dispatch one batched decode for the running sequences, THEN
        read what earlier steps left in flight. ``serve/decode_step``
        holds this round's dispatch (``serve/decode_dispatch``) and the
        wait for the previous round's tokens (``serve/token_wait``, in
        ``_take``); ``serve/sample`` counts those (and, in a step
        without a decode round, holds the wait too)."""
        if not decodes and not self._in_flight:
            return
        fetched = None
        if decodes:
            with _mspans.span("serve/decode_inputs"):
                pos = np.zeros((self.max_batch,), np.int32)
                act = np.zeros((self.max_batch,), bool)
                bts = np.zeros((self.max_batch, self.pages_per_seq),
                               np.int32)
                for seq in decodes:
                    slot = seq.slot
                    pos[slot] = seq.num_dispatched - 1
                    act[slot] = True
                    bts[slot] = self._bt_row(seq)
                    seq.num_cached = seq.num_dispatched
                # a round's time has always counted its uploads
                t0 = time.perf_counter()
                batch = (jnp.asarray(bts), jnp.asarray(pos),
                         self._last_tok, jnp.asarray(act))
            with _mspans.span("serve/decode_step", n_active=len(decodes)):
                if any(e.decode for e in self._in_flight):
                    _mhooks.counter("serve/rounds_overlapped")
                # the host's cost of sending one decode round out: the
                # call into the runtime and the start of the async copies
                with _mspans.span("serve/decode_dispatch"):
                    logits, self._last_tok, self.state, aux = \
                        self._decode(self.params, self.state, *batch)
                    self._hold([(s, s.slot) for s in decodes], logits,
                               aux, decode=True, t_dispatch=t0)
                fetched = self._take(self._due(True))
        with _mspans.span("serve/sample"):
            if fetched is None:
                fetched = self._take(self._due(False))
            for f in fetched:
                self._commit(*f)

    def _record_step_gauges(self) -> None:
        """Pool-occupancy + queue-state gauges, once per scheduler
        round (the Watchdog's serve-side inputs). One `enabled` read
        when detached."""
        if not _mhooks.enabled():
            return
        alloc = self.sched.allocator
        used = alloc.num_pages - 1 - alloc.free_pages
        _mhooks.gauge("serve/pages_in_use", used)
        _mhooks.gauge("serve/pages_free", alloc.free_pages)
        _mhooks.gauge("serve/pages_total", alloc.num_pages - 1)
        _mhooks.gauge("serve/pool_bytes_in_use",
                      self.ccfg.occupancy_bytes(used))
        _mhooks.gauge("serve/queue_depth", len(self.sched.waiting))
        if self.sched.waiting:
            oldest = min(s.queued_t for s in self.sched.waiting)
            _mhooks.gauge("serve/queue_wait_oldest_s",
                          max(0.0, time.perf_counter() - oldest))
        else:
            _mhooks.gauge("serve/queue_wait_oldest_s", 0.0)

    def preempt(self, seq_id: int) -> None:
        """Force-preempt a running sequence (tests/benchmarks; the
        organic path is the scheduler's evict-on-exhaustion). Its
        tokens in flight are read first: it re-enters by their values."""
        self._drain("preempt")
        for seq in self.sched.running:
            if seq.seq_id == seq_id:
                self.sched._preempt(seq)
                self._free_slot(seq)
                return
        raise KeyError(f"sequence {seq_id} is not running")

    def run(self, max_steps: int = 100_000) -> Dict[int, List[int]]:
        """Drive until every request finished; returns seq_id ->
        generated tokens for EVERY request ever added (including ones
        that already finished during earlier manual ``step()`` calls)."""
        steps = 0
        t0 = time.perf_counter()
        tok0 = self.tokens_generated
        try:
            while self.sched.has_work:
                self.step()
                steps += 1
                if steps > max_steps:
                    raise RuntimeError("serve engine did not drain "
                                       f"in {max_steps} steps")
        except BaseException:
            # abort path: leave the black box (in-flight request spans
            # are still open — the flight dump names them). Inert
            # unless flight.install() armed dumps.
            _mflight.trigger("serve/abort")
            raise
        finally:
            # engine shutdown: snapshot the final SLO/occupancy state
            # (no-op unless the flight recorder is armed)
            _mflight.trigger("serve/shutdown")
        self._record_run_summary(t0, tok0)
        return {sid: s.tokens[len(s.prompt):]
                for sid, s in self.seqs.items()}

    def _record_run_summary(self, t0: float, tok0: int) -> None:
        """Goodput gauge + histogram-snapshot flush at drain time (one
        `enabled` read when detached)."""
        if not _mhooks.enabled():
            return
        dt = time.perf_counter() - t0
        toks = self.tokens_generated - tok0
        if dt > 0 and toks:
            # tokens/s/chip goodput: completed-token throughput per
            # participating chip (the serve twin of training MFU —
            # monitor.attribution.mfu)
            _mhooks.gauge("serve/goodput_tokens_per_sec_chip",
                          toks / dt / max(1, self.tp))
        rec = _monitor_state.recorder
        if rec is not None:
            # cumulative SLO histograms ride the ring/stream, so a
            # crash after drain still leaves the percentiles on disk
            rec.emit_histograms()


def naive_generate(cfg: GPTConfig, params, requests, *, max_seq_len: int,
                   attention_impl: Optional[str] = None):
    """The full-recompute baseline: same batched greedy decoding, NO
    KV cache — every token recomputes the whole prefix (one fixed-shape
    forward over the padded context per step). The bench's honesty
    anchor for the paged-cache speedup.

    ``requests``: list of ``(prompt, max_new_tokens)``. Returns
    ``(outputs: list[list[int]], step_times: list[float])``.
    """
    _, p_impl = _default_impls()
    impl = attention_impl or p_impl
    B = len(requests)
    S = max_seq_len

    @jax.jit
    def step(ids, lengths):
        logits = model_mod.full_forward_logits(cfg, params, ids, lengths,
                                               attention_impl=impl)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    ids = np.zeros((B, S), np.int32)
    lengths = np.zeros((B,), np.int32)
    todo = np.zeros((B,), np.int32)
    for i, (prompt, n_new) in enumerate(requests):
        ids[i, :len(prompt)] = prompt
        lengths[i] = len(prompt)
        todo[i] = n_new
    outputs: List[List[int]] = [[] for _ in range(B)]
    step_times: List[float] = []
    while (np.array([len(o) for o in outputs]) < todo).any():
        t0 = time.perf_counter()
        next_toks = np.asarray(step(jnp.asarray(ids), jnp.asarray(lengths)))
        step_times.append(time.perf_counter() - t0)
        for i in range(B):
            if len(outputs[i]) < todo[i]:
                outputs[i].append(int(next_toks[i]))
                ids[i, lengths[i]] = next_toks[i]
                lengths[i] += 1
    return outputs, step_times
