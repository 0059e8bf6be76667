"""The limits of `correct`, read on the chip: the program over many seeds,
and the control, from one set-up.

    python benchmark/control.py --workload <cell> --seeds 11,12,13 \
        --seconds 20

One process boots the cell once. For each seed it plays a window at the
cell's own load on that seed's traffic, then reads every compared number
over that window's blocks twice: for the program (the headers it built,
and the guarantees of the run) and for the control. The benchmark's own
runs never run this.

The control is the reference put in the program's place with one of the
configuration's guarantees broken: its state commit leaves out the last
account each block changed (a commit that loses one dirty leaf, as a
partial or deferred commit would). A limit holds only where the
program's readings stay within it and the control's do not.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import Reference, compare  # noqa: E402


class DroppedLeaf(Reference):
    """The reference whose commit loses the last dirty account a block."""

    def _commit_accounts(self, touched) -> None:
        super()._commit_accounts(sorted(touched)[:-1])


class Readings:
    """Incremental replays: the reference, and the control beside it."""

    def __init__(self, genesis):
        self.ref = Reference(genesis)
        self.ctl = DroppedLeaf(genesis)
        self.done = 0

    def next(self, accepted, signed_by, genesis_root):
        """(program, control) numbers over the blocks not yet read."""
        prog_rows, ctl_rows = [], []
        for blob in accepted[self.done:]:
            r = self.ref.apply_block(blob, signed_by.get)
            c = self.ctl.apply_block(blob, signed_by.get)
            prog_rows.append(r)
            ctl_rows.append({**r, "header_root": c["root"],
                             "header_receipts": c["receipts_root"],
                             "header_gas_used": c["gas_used"]})
        self.done = len(accepted)
        prog = {"genesis_root_diff": int(self.ref.genesis_root
                                         != genesis_root),
                **compare(prog_rows)}
        ctl = {"genesis_root_diff": int(self.ref.genesis_root
                                        != self.ctl.genesis_root),
               **compare(ctl_rows)}
        return ({k: {"value": v, "max": 0} for k, v in prog.items()},
                {k: {"value": v, "max": 0} for k, v in ctl.items()})


def main(argv=None, root: str = ROOT, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from benchmark.harness import Cell, Session, SetupError, within

    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        session = Session(Cell(root, args.workload), seeds[0], require_tpu)
    except SetupError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    readings = Readings(session.genesis)
    all_sound = all_caught = True
    try:
        for i, seed in enumerate(seeds):
            if i:
                session.reseed(seed)
            rec = session.window(args.seconds)
            prog, ctl = readings.next(session.accepted, session.signed_by,
                                      session.genesis_root)
            prog.update(session.guarantee_checks(rec))
            sound = all(within(c) for c in prog.values())
            caught = sorted(k for k, c in ctl.items() if not within(c))
            all_sound &= sound
            all_caught &= bool(caught)
            print("seed " + json.dumps({
                "seed": seed, "window_blocks": rec.n_blocks,
                "block_s": [b["block_s"] for b in rec.blocks],
                "program": {k: c["value"] for k, c in prog.items()},
                "program_correct": sound,
                "control": {k: c["value"] for k, c in ctl.items()},
                "control_fails": caught}), flush=True)
    finally:
        session.close()
    print("summary " + json.dumps({
        "seeds": len(seeds), "program_correct_on_every_seed": all_sound,
        "control_failed_on_every_seed": all_caught}), flush=True)
    return 0


if __name__ == "__main__":
    # set before JAX loads, inside the checkout as benchmark/run.py does
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    # cache every program set-up compiles, the small ones too
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.exit(main())
