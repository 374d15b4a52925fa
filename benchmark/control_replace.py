"""The replacement cell's controls: ``correct`` shown to fail, once for
each guarantee the driver can break in the reference
(``drivers/engine_replace.CONTROLS``).

    python3 benchmark/control_replace.py --workload <name> --seed <n> [--seconds <s>]

Stands beside ``control_reconf.py`` and runs its ``main`` as it is (not
an edit of it: that script takes the cases from the cell's own driver
and judges each by the sampled replicas' history, which is what tells
here too). **A snapshot restored without its ConfState** (the parent
program's snapshot handler: the log is taken, the configuration held
is kept): the fresh replica follows and is swapped in but never learns
that it is a member, so its masks and its history differ from the
program's, where the snapshot states the configuration and the replica
takes it; later it honours no hand-over and a group can lose its
leader for good. **Commit on the incoming majority alone**:
with the old machine off and the transfers' target away the outgoing
half has no majority; the reference commits through those rounds all
the same, the program stalls, and only the history tells once the node
is back. The engine is built and run at the cell's own size (so this
needs the chip) by the cell's own generator, and compared with the
reference once sound and once under each control. Prints one line a
case (``derailed_groups`` among its numbers) and exits 0 only if the
sound case is correct and no control is. The benchmark's own runs never
call this.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path[0] = ROOT
    from benchmark.control_reconf import main

    os._exit(main())
