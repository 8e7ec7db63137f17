"""Seconds of a step's ``tft.step`` span that none of its pieces covers
(``quorum.start``, ``shard_batch``, ``grads``, ``resolve_speculation``,
``exchange``, ``commit``, ``apply``, ``loss_sync``, on the main thread): what
``FTTrainer.step`` spends between them — dropping the last reference to a
tree, for one. Median over the traced steps, mean over groups."""

import program_spans

NAME, UNIT, SOURCE = "step_unattributed_s", "s", "program_span"
LAYER = "ft loop"
MOVES = "step_p50_s"


def compute(run):
    name = program_spans.PREFIX + "step"
    return program_spans.per_step_median(run, lambda t: t.self_seconds(name))
