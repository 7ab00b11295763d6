"""Host spans and counters of the FL round.

The spans are JAX profiler annotations: written into the profiler's own
trace, on the clock of the device's ``XLA Ops``, and nothing at all when
no profiler session is active.  Wrap a run in
``jax.profiler.trace(log_dir)`` to record them.  Every child span
carries the round it serves as its ``round`` argument.

======================  ==================================================
span                    covers
======================  ==================================================
``fl.round`` (step r)   one round of a driver (round-ahead, step r
                        reads and checkpoints round r-1)
``fl.fence``            the cohort-gather device read of the prefix state
``fl.elect_rerun``      the dense re-run of an overflowed windowed election
``fl.cohort``           round keys, cohort slicing, upload, trainer and
                        FedAvg dispatch
``fl.dispatch``         the accuracy dispatch (round-ahead: the next
                        round's prefix, ``prefix_round``, then the eval,
                        ``eval_round``)
``fl.read``             the accuracy read and the round's row
``fl.checkpoint``       the checkpointer hook
======================  ==================================================

Inside the jitted prefix the stages carry ``jax.named_scope`` names
(``positions``, ``probe``, ``elect``, ``deadline``), which the compiled
HLO keeps in each instruction's ``op_name``.  Never open a span inside
jitted code: it would run once, at trace time.

``RoundCounters`` are plain ints kept on ``FLSimulation.counters``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax

ROUND = "fl.round"
FENCE = "fl.fence"
ELECT_RERUN = "fl.elect_rerun"
COHORT = "fl.cohort"
DISPATCH = "fl.dispatch"
READ = "fl.read"
CHECKPOINT = "fl.checkpoint"

# named scopes of the selection prefix's stages
SCOPES = ("positions", "probe", "elect", "deadline")


def round_span(rnd: int) -> jax.profiler.StepTraceAnnotation:
    """The ``fl.round`` step span of round ``rnd``."""
    return jax.profiler.StepTraceAnnotation(ROUND, step_num=rnd)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span; arguments that are None are left out."""
    return jax.profiler.TraceAnnotation(
        name, **{k: v for k, v in args.items() if v is not None})


@dataclass
class RoundCounters:
    """What the rounds did, counted on the host.

    - ``rounds``: prefix states gathered at the fence;
    - ``elect_reruns``: of those, windowed elections that overflowed and
      re-ran the prefix with the dense election;
    - ``cohort_rows``: survivors trained (real cohort rows);
    - ``cohort_pad_rows``: padding slots trained at weight zero to fill
      a cohort bucket;
    - ``evals_behind_prefix``: rounds whose accuracy eval the
      round-ahead driver queued behind the next round's prefix.
    """
    rounds: int = 0
    elect_reruns: int = 0
    cohort_rows: int = 0
    cohort_pad_rows: int = 0
    evals_behind_prefix: int = 0

    def add(self, other: "RoundCounters") -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))

    def __str__(self) -> str:
        return (f"{self.rounds} rounds, {self.elect_reruns} election "
                f"re-runs, {self.cohort_rows} cohort rows trained + "
                f"{self.cohort_pad_rows} padding, "
                f"{self.evals_behind_prefix} evals behind the next prefix")
