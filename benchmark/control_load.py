"""The load cell's controls: ``correct`` shown to fail, once for each
set of offers the driver can step the reference on in the place of the
one the program drew (``drivers/engine_load.CONTROLS``).

    python3 benchmark/control_load.py --workload <name> --seed <n> [--seconds <s>]

Stands beside ``control_trickle.py`` and, like it, runs
``control_reconf.py``'s ``main`` as it is (that script takes the cases
from the cell's own driver and judges each by the sampled replicas'
history). **The draws a round late**: the reference offers every group
in round t what the program drew for round t - 1; the totals hardly
move, so what tells is the sampled replicas' history, and the
conservation law for the groups whose first or last round differed.
**Uniform popularity**: the reference's thresholds are those of a key
space without skew at the same ``ops_per_group_round``; the hot groups
are then offered a fraction of what the program offered them and the
cold ones many times more, and the conservation law fails for nearly
every group. The engine is built and run at the cell's own size (so
this needs the chip) by the cell's own generator, and compared with the
reference once sound and once under each control. Prints one line a
case and exits 0 only if the sound case is correct and no control is.
The benchmark's own runs never call this.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path[0] = ROOT
    from benchmark.control_reconf import main

    os._exit(main())
