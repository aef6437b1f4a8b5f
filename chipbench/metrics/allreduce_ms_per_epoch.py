"""Device time of the all-reduces inside the solve spans per epoch the
batches needed, on the slowest chip (cells on several chips).

XLA names an all-reduce that ``jax.lax.psum`` or ``pmean`` put in after the
primitive (``%psum.25``), not after its opcode, so ``trace.COLLECTIVE``
does not see the consensus all-reduce; this reader matches both. The time
includes what a chip waits inside the all-reduce for the other chips."""
import re

from chipbench import trace

ALL_REDUCE = re.compile(r"^%?(psum|pmean|all-reduce)([.-]|$)")


def read(run):
    sub = run.cell_trace()
    if sub is None or not run.batches or len(sub.chips) < 2:
        return None
    per_chip = [
        trace.busy(sub, c, within=run.solve_spans(), only=ALL_REDUCE)
        for c in sub.chips
    ]
    return 1e3 * max(per_chip) / run.live_epochs()
