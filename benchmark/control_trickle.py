"""The trickle cell's controls: ``correct`` shown to fail, once for
each schedule the driver can step the reference on in the place of the
one the program ran (``drivers/engine_trickle.CONTROLS``).

    python3 benchmark/control_trickle.py --workload <name> --seed <n> [--seconds <s>]

Stands beside ``control_replace.py`` and, like it, runs
``control_reconf.py``'s ``main`` as it is (that script takes the cases
from the cell's own driver and judges each by the sampled replicas'
history). **The starts shifted by one round**: the reference moves
every sampled group of a batch a round after the program did; the
states meet again once a move is done, so only the history, and the
groups still in motion, tell. **Every group on the lockstep schedule**
(the phased argument dropped): the reference moves every sampled group
from round 0, as the lockstep cell would; a group the rebalancer never
started then ends with another configuration. The engine is built and
run at the cell's own size (so this needs the chip) by the cell's own
generator, and compared with the reference once sound and once under
each control. Prints one line a case and exits 0 only if the sound
case is correct and no control is. The benchmark's own runs never call
this.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path[0] = ROOT
    from benchmark.control_reconf import main

    os._exit(main())
