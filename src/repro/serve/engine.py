"""Serving: prefill/decode step factories + a batched engine.

``make_prefill_fn`` / ``make_decode_fn`` produce the exact programs the
dry-run lowers for the ``prefill_32k`` / ``decode_32k`` / ``long_500k``
cells. The ``ServeEngine`` adds the operational layer a deployment needs:
request queue, continuous batching into fixed decode slots, greedy/top-k
sampling, and **straggler mitigation** — a request that exceeds its decode
deadline is evicted and re-queued (bounded retries), so one stuck stream
cannot head-of-line-block the batch.

``ServeEngine.run`` names its phases for the profiler with
``jax.profiler.TraceAnnotation`` spans, each carrying its counts as span
arguments (``batch`` is the id all spans of one batch share):

  serve.batch     one batch, to its last token    batch, rows, prompt_len
  serve.prefill   padding, host->device, dispatch batch, rows, prompt_len
  serve.sample    argmax, fetch to the host       batch, step
  serve.schedule  append, retire, evict, requeue  batch, step, live, evicted
  serve.decode    feeding the tokens, dispatch    batch, step, pos

The last four nest in ``serve.batch``; ``step`` 0 is the token that
prefill gives, step n the n-th decode step, and ``pos`` the cache position
that step writes. ``live`` counts the rows still generating after the
loop and ``evicted`` the rows it evicted. With the profiler off a span
costs about a microsecond.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import ShardingRules, use_rules
from ..models.model import Model

__all__ = ["make_prefill_fn", "make_decode_fn", "ServeEngine", "Request"]

_span = jax.profiler.TraceAnnotation


def make_prefill_fn(model: Model, rules: Optional[ShardingRules],
                    smax: int) -> Callable:
    def prefill(params, batch):
        with use_rules(rules):
            return model.prefill(params, batch, smax)

    return prefill


def make_decode_fn(model: Model, rules: Optional[ShardingRules]) -> Callable:
    def decode(params, cache, tokens):
        with use_rules(rules):
            return model.decode_step(params, cache, tokens)

    return decode


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # [S] int32
    max_new: int
    generated: List[int] = field(default_factory=list)
    retries: int = 0
    deadline_steps: Optional[int] = None  # straggler budget per request
    steps_used: int = 0

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new


class ServeEngine:
    """Single-slot-group batched decoder (greedy sampling).

    Not a throughput-optimal server — it is the *correctness* reference for
    the serving programs plus the scheduling/straggler logic, which TPU-EM
    simulates at pod scale.
    """

    def __init__(self, model: Model, params, *, smax: int,
                 rules: Optional[ShardingRules] = None,
                 max_retries: int = 1, jit: bool = True):
        self.model = model
        self.params = params
        self.smax = smax
        self.rules = rules
        self.max_retries = max_retries
        pf, dc = make_prefill_fn(model, rules, smax), make_decode_fn(model, rules)
        self.prefill_fn = jax.jit(pf) if jit else pf
        self.decode_fn = jax.jit(dc, donate_argnums=(1,)) if jit else dc
        self.queue: Deque[Request] = deque()
        self.completed: Dict[int, Request] = {}
        self.evicted: List[int] = []
        self.evicted_partial: Dict[int, Request] = {}
        self._rid = 0
        self._batch_id = 0

    def submit(self, prompt: np.ndarray, max_new: int = 16,
               deadline_steps: Optional[int] = None) -> int:
        self._rid += 1
        self.queue.append(Request(self._rid, np.asarray(prompt, np.int32),
                                  max_new, deadline_steps=deadline_steps))
        return self._rid

    def _prefill_batch(self, reqs: List[Request]):
        S = max(len(r.prompt) for r in reqs)
        toks = np.zeros((len(reqs), S), np.int32)
        for i, r in enumerate(reqs):
            toks[i, S - len(r.prompt):] = r.prompt  # left-pad (simple)
        batch = {"tokens": jnp.asarray(toks)}
        logits, cache = self.prefill_fn(self.params, batch)
        return logits, cache

    def run(self, batch_size: int = 4) -> Dict[int, List[int]]:
        """Drain the queue; returns {rid: generated tokens}.

        Permanently-evicted stragglers (retry budget exhausted) keep
        their rid in ``self.evicted`` AND contribute whatever they
        generated to the returned mapping — a stalled stream's partial
        output is still an answer the caller paid for.
        """
        while self.queue:
            reqs = [self.queue.popleft() for _ in
                    range(min(batch_size, len(self.queue)))]
            self._batch_id += 1
            b = self._batch_id
            rows, prompt_len = len(reqs), max(len(r.prompt) for r in reqs)
            with _span("serve.batch", batch=b, rows=rows,
                       prompt_len=prompt_len):
                with _span("serve.prefill", batch=b, rows=rows,
                           prompt_len=prompt_len):
                    logits, cache = self._prefill_batch(reqs)
                live = list(range(rows))
                step, pos = 0, prompt_len
                while True:
                    with _span("serve.sample", batch=b, step=step):
                        next_tok = np.asarray(jnp.argmax(logits, -1),
                                              np.int32)
                    with _span("serve.schedule", batch=b, step=step) as sp:
                        evicted = self._schedule(reqs, live, next_tok)
                        sp.set_metadata(live=len(live), evicted=evicted)
                    if not live:
                        break
                    step += 1
                    with _span("serve.decode", batch=b, step=step, pos=pos):
                        logits, cache = self.decode_fn(
                            self.params, cache,
                            jnp.asarray(next_tok)[:, None])
                    pos += 1
        out = {rid: r.generated for rid, r in self.completed.items()}
        out.update({rid: r.generated
                    for rid, r in self.evicted_partial.items()})
        return out

    def _schedule(self, reqs: List[Request], live: List[int],
                  next_tok: np.ndarray) -> int:
        """Append each live row's token; retire finished rows and evict
        stragglers from ``live`` (re-queued while retries remain).
        Returns the number of rows evicted."""
        evicted = 0
        for i in list(live):
            r = reqs[i]
            r.generated.append(int(next_tok[i]))
            r.steps_used += 1
            if r.done:
                live.remove(i)
                self.completed[r.rid] = r
            elif (r.deadline_steps is not None
                  and r.steps_used >= r.deadline_steps):
                # straggler: evict; re-queue with remaining budget
                live.remove(i)
                evicted += 1
                if r.retries < self.max_retries:
                    r.retries += 1
                    r.steps_used = 0
                    self.queue.append(r)
                else:
                    self.evicted.append(r.rid)
                    self.evicted_partial[r.rid] = r
        return evicted
