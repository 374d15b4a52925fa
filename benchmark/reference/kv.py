"""The served cells' plain reference, and their control.

``PlainCluster`` is the same semantics with none of the program: each
member a list of per-group dicts; a put goes to the group's leader, is
copied to every member, and only then is acknowledged (applied on the
leader); a linearizable read returns the leader's applied value. It
offers the client-side calls ``drivers/served.py`` offers, so the
generator drives it unchanged — in the tests, and as the *control*: with
one guarantee of the configuration broken it stands in the program's
place and the comparison has to come out ``correct: false``.

``broken`` names the guarantee broken:

* ``"ack_before_replication"`` — the leader acknowledges at once and
  one follower never gets every ``LOSE_EVERY``-th put (an acknowledged
  write lost on a member);
* ``"stale_read"`` — every ``LOSE_EVERY``-th linearizable read is served
  from a member that has not applied the put (a stale answer);
* ``"no_fsync"`` — the members report no WAL fsync.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

LOSE_EVERY = 7
# A read waits for a round in the program; a reference that answered at
# once would have its reading clients spin on the interpreter lock.
READ_WAIT_S = 0.002
BROKEN = ("ack_before_replication", "stale_read", "no_fsync")


class Retry(Exception):
    pass


class PlainCluster:
    Retry = Retry

    def __init__(self, groups: int, members: int = 3,
                 broken: Optional[str] = None) -> None:
        if broken is not None and broken not in BROKEN:
            raise ValueError(f"broken must be one of {BROKEN}")
        self.groups = groups
        self.broken = broken
        self.members = list(range(members))
        self.kvs: List[List[Dict[bytes, bytes]]] = [
            [{} for _ in range(groups)] for _ in self.members]
        self.applied = [np.zeros(groups, np.int64) for _ in self.members]
        self.lead = (np.arange(groups) % members) + 1
        self.puts = 0
        self.reads = 0
        self.fsyncs = [0 for _ in self.members]
        self._lock = threading.Lock()
        self._win = [None, None]

    # -- what a client can do (as drivers/served.py) -----------------------------

    def leaders(self) -> np.ndarray:
        return self.lead.copy()

    def propose(self, member: int, group: int, key: bytes,
                value: bytes) -> bool:
        if self.lead[group] != member + 1:
            return False
        with self._lock:
            self.puts += 1
            lose = (self.broken == "ack_before_replication"
                    and self.puts % LOSE_EVERY == 0)
            victim = (member + 1) % len(self.members)
            for m in self.members:
                if lose and m == victim:
                    continue
                self.kvs[m][group][key] = value
                self.applied[m][group] += 1
                if self.broken != "no_fsync":
                    self.fsyncs[m] += 1
        return True

    def applied_marks(self, member: int) -> np.ndarray:
        return self.applied[member].copy()

    def applied_value(self, member: int, group: int,
                      key: bytes) -> Optional[bytes]:
        return self.kvs[member][group].get(key)

    def lread(self, member: int, group: int, key: bytes,
              timeout: float) -> Optional[bytes]:
        if self.lead[group] != member + 1:
            raise Retry(f"group {group}: member {member} does not lead")
        time.sleep(READ_WAIT_S)
        with self._lock:
            self.reads += 1
            if (self.broken == "stale_read"
                    and self.reads % LOSE_EVERY == 0):
                return None  # as a member that never applied the put
        return self.kvs[member][group].get(key)

    # -- counters, as far as the comparison reads them ------------------------------

    def window_opens(self) -> None:
        self._win[0] = list(self.fsyncs)

    def window_closes(self) -> None:
        self._win[1] = list(self.fsyncs)

    def sample(self) -> None:
        pass

    def window_counters(self) -> dict:
        return {}

    def checks(self, raw: dict, check_lread: bool):
        """The cell's comparison over what the generator counted on this
        cluster (``raw`` of ``generators/kv_closed.run``)."""
        from ..compare import served_checks

        lreads = None
        if check_lread:
            lreads = list(raw["lreads"])
            for g, k in list(raw["acked"])[:64]:
                lreads.append(
                    (g, k, self.lread(int(self.lead[g]) - 1, g, k, 1.0)))
        win = None
        if raw["clients_putting"]:
            win = [b - a for a, b in zip(*self._win)]
        return served_checks(raw["acked"], raw["proposed"], self.kvs,
                             self.fsyncs, win, lreads, None, [])
