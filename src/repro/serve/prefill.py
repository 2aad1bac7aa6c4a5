"""Chunked batched prefill planner for the serving engine.

Until now the engine admitted every request by teacher-forcing its
prompt through the B=1-token decode step, one position per engine step:
a 100-token prompt cost 100 full-batch decode steps of latency before
the first generated token, and every one of those steps streamed the
whole compressed weight stack to advance a single position per slot.
Prefill is exactly where the bitmap weight stream amortizes — EIE and
CoDR both make the case that compressed-weight reuse pays off when many
activations share one fetched weight tile, and decode (M=1) is the
worst case while prefill (M=chunk) is the best.

The planner turns waiting prompts into fixed-shape prefill calls:

* each admitted request's prompt positions ``0 .. len(prompt)-2`` are
  split into fixed ``chunk``-token pieces (the last prompt token is
  *not* prefilled — it feeds the first real decode step, which samples
  the first generated token exactly like the teacher-forcing path did);
* every engine step, chunks from **all** slots currently mid-prefill are
  batched into one padded ``(num_slots, chunk)`` call — one jit
  signature regardless of how many requests are prefilling, with
  padding lanes masked by a per-slot length vector;
* the engine budgets **at most one prefill call per engine step**, so a
  stream of long prompts cannot starve the decode slots: prefill and
  decode interleave step for step, decode keeps running at full batch
  width, and prefilling slots ride the decode batch as masked
  passengers until their cache is resident.

The planner is pure host-side bookkeeping — the device work is
``models.model.prefill_hidden`` via ``launch.steps.build_prefill_step``.

Invariants (equivalence-tested in tests/test_prefill.py and the
full-matrix test in tests/test_packed_streaming.py):

* **Matches teacher-forcing** — ``prefill_hidden`` attends the whole
  chunk in one blocked causal call and writes its K/V lines in one
  scatter: the cache is bit-identical to the ``prefill_chunk=0`` legacy
  walk's, each token attends the same (position, K/V) pairs (only the
  order of the softmax and value sums differs), and the served tokens
  match, across windows/ring wraps, MoE (chunk folded into the batch
  dim so capacity matches decode), contiguous and paged caches, dense
  and packed weight streams.
* **The last prompt token is never prefilled** — it feeds the first
  real decode step, which samples the first generated token exactly
  like the teacher-forcing path did.
* **One jit signature** — every call is a padded ``(num_slots, chunk)``
  batch with a per-slot length mask; ``lens == 0`` lanes write nothing
  (contiguous lanes drop out of the scatter, paged lanes hit the trash
  page).
* **At most one prefill call per engine step** — decode never starves;
  mid-prefill slots ride the decode batch as masked passengers parked
  on their next unwritten position.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Set, Tuple

import numpy as np

from repro.serve.errors import AuditViolation


@dataclasses.dataclass
class PrefillJob:
    """One slot's remaining prompt ingestion."""

    prompt: List[int]
    next: int            # next prompt position to prefill
    end: int             # stop (exclusive): len(prompt) - 1


class PrefillPlanner:
    """Splits admitted prompts into chunks and batches them into calls.

    ``start(slot, prompt)`` registers a slot whose prompt needs
    prefilling (returns False for single-token prompts, which go
    straight to decode); ``next_call()`` assembles one padded
    ``(num_slots, chunk)`` batch covering every registered slot's next
    chunk and advances the plan.  The engine calls ``next_call`` at most
    once per step while ``has_work``.
    """

    def __init__(self, num_slots: int, chunk: int):
        assert chunk > 0
        self.num_slots = num_slots
        self.chunk = chunk
        self._jobs: Dict[int, PrefillJob] = {}
        self.calls = 0
        self.tokens_prefilled = 0

    # ------------------------------------------------------------ plan ----

    def start(self, slot: int, prompt: Sequence[int],
              start: int = 0) -> bool:
        """Register a freshly admitted slot; False = nothing to prefill
        (the prompt is a single token — decode consumes it directly).

        ``start`` skips positions already resident in the slot's cache —
        the shared-prefix hit path: adopted pages cover ``0 .. start-1``,
        so prefill begins at ``start`` (a full hit, ``start >= end``,
        skips prefill entirely and TTFT collapses to queue +
        first-decode)."""
        assert slot not in self._jobs, f"slot {slot} already prefilling"
        end = len(prompt) - 1
        if end - start <= 0:
            return False
        self._jobs[slot] = PrefillJob(list(prompt), start, end)
        return True

    def cancel(self, slot: int) -> None:
        """Drop a slot's remaining plan (preemption): the engine
        re-ingests the whole prefix on re-admission."""
        self._jobs.pop(slot, None)

    @property
    def has_work(self) -> bool:
        return bool(self._jobs)

    def in_prefill(self, slot: int) -> bool:
        return slot in self._jobs

    def next_pos(self, slot: int) -> int:
        """The slot's next unwritten prompt position — the engine parks
        the slot's decode-passenger write there (the next chunk rewrites
        it, so the junk line is never read)."""
        return self._jobs[slot].next

    # ------------------------------------------------------------ call ----

    def next_call(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 List[int]]:
        """Assemble one batched prefill call and advance the plan.

        Returns ``(tokens (num_slots, chunk) int32, pos (num_slots,)
        int32, lens (num_slots,) int32, finished slots)`` — every
        registered slot contributes its next ``<= chunk`` prompt tokens;
        rows with ``lens == 0`` are padding lanes the device masks off.
        Slots whose last chunk this is are returned in ``finished`` and
        leave the plan (the engine flips them to decode phase).
        """
        assert self._jobs, "next_call with no prefill work"
        tokens = np.zeros((self.num_slots, self.chunk), np.int32)
        pos = np.zeros(self.num_slots, np.int32)
        lens = np.zeros(self.num_slots, np.int32)
        finished: List[int] = []
        for slot in sorted(self._jobs):
            job = self._jobs[slot]
            n = min(self.chunk, job.end - job.next)
            tokens[slot, :n] = job.prompt[job.next:job.next + n]
            pos[slot] = job.next
            lens[slot] = n
            job.next += n
            if job.next >= job.end:
                finished.append(slot)
        for slot in finished:
            del self._jobs[slot]
        self.calls += 1
        self.tokens_prefilled += int(lens.sum())
        return tokens, pos, lens, finished

    # ------------------------------------------------------------ audit ----

    def audit(self, active_slots: Set[int]) -> None:
        """Planner invariants (raises ``AuditViolation``): every job
        belongs to a currently active slot (a cancelled/retired slot
        must not keep a plan), and its cursor stays inside the prompt."""
        for slot, job in self._jobs.items():
            if slot not in active_slots:
                raise AuditViolation(
                    f"prefill job for slot {slot} which is not active")
            if not (0 <= job.next <= job.end <= len(job.prompt)):
                raise AuditViolation(
                    f"prefill cursor out of range for slot {slot}: "
                    f"next={job.next} end={job.end} "
                    f"prompt={len(job.prompt)}")

    # --------------------------------------------------------- reports ----

    def register_metrics(self, reg) -> None:
        reg.gauge("prefill.calls", lambda: self.calls)
        reg.gauge("prefill.tokens", lambda: self.tokens_prefilled)
        reg.gauge("prefill.in_flight", lambda: len(self._jobs))

    def report(self) -> Dict:
        lanes = self.calls * self.num_slots * self.chunk
        return {
            "chunk": self.chunk,
            "calls": self.calls,
            "tokens_prefilled": self.tokens_prefilled,
            "in_flight": len(self._jobs),
            "lane_utilization": (self.tokens_prefilled / lanes
                                 if lanes else None),
        }
