"""``serve-lfm2-longgen-backlog`` run through the harness with ONE fault
planted in the program's handling of the short convolutions' tail, to show
that the cell's comparison refuses it: each has to come out ``"correct":
false`` (the runner's lines above the result say by which reading).

``tail_at_padded_length``
    the prefill hands over the tail at the end of the PADDED row, not at
    the row's true length (``ops/ssd.py:conv_tail``)
``tail_kept``
    an admission leaves the slot's tail as it was: zeros in a slot never
    used, the last request's in one readmitted (the engine's merge)
``tail_kept_where_the_prime_is_short``
    an admission writes only the tail rows its prime reaches: a prime of
    one token leaves one row of the last request's, where a zero belongs

Run once, on the chip, a fault a process; not part of a run of the cell.

    python3 perf/tools/lfm2_faults.py --fault <name> --seed <n>
"""

import argparse
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def padded_tail():
    from progen_tpu.ops import ssd

    true_tail = ssd.conv_tail

    def conv_tail(u, lengths, k):
        return true_tail(u, lengths * 0 + u.shape[1], k)

    return mock.patch.object(ssd, "conv_tail", conv_tail)


def kept_tail(short_only: bool):
    import jax
    import jax.numpy as jnp

    from progen_tpu.decode.paging import SlotCaches

    def merge(self, take, caches, hstate, gate_rows, operands):
        slots = jax.tree.leaves(caches)[0].shape[0]
        length = take(hstate["pos"], jnp.zeros((slots,), jnp.int32))

        def one(path, h, old):
            taken = take(h, old)
            if path[-1].key != "conv":
                return taken
            if not short_only:
                return old
            k = old.shape[1]
            unreached = length[:, None] - k + jnp.arange(k)[None] < 0
            return jnp.where(unreached[..., None], old, taken)

        return jax.tree_util.tree_map_with_path(one, hstate["caches"], caches)

    return mock.patch.object(SlotCaches, "merge", merge)


FAULTS = {
    "tail_at_padded_length": padded_tail,
    "tail_kept": lambda: kept_tail(False),
    "tail_kept_where_the_prime_is_short": lambda: kept_tail(True),
}


CELL = "serve-lfm2-longgen-backlog"
SECONDS = 2.0       # the window: the comparison is made in set-up


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--fault", required=True, choices=sorted(FAULTS))
    parser.add_argument("--seed", type=int, default=46)
    args = parser.parse_args(argv)

    from perf.lib import harness

    with FAULTS[args.fault]():
        result = harness.run_cell(CELL, args.seed, SECONDS, False,
                                  time.perf_counter())
    print(json.dumps({"fault": args.fault, "seed": args.seed,
                      "correct": result["correct"]}), flush=True)
    return int(result["correct"])       # 0: the fault was refused


if __name__ == "__main__":
    sys.exit(main())
