"""The load cell's plain reference (``engine_shadow_load``):
``reference.shadow_reconf.ReconfCluster`` (beside this file, frozen,
not edited) stepped on *one group's own offers*, and the draws that
make them, in plain numpy.

The deployment offers no two groups the same thing: in round t group g
is offered ``n`` updates and asked a read or not, drawn from (seed, t,
g) against the group's two thresholds. The rule, from the
configuration's words (``assumed.draws``), written here on its own and
imported from nowhere: with all arithmetic in unsigned 32 bits,

    fmix32(x):  x ^= x >> 16;  x *= 0x85EBCA6B;  x ^= x >> 13;
                x *= 0xC2B2AE35;  x ^= x >> 16        (murmur3's finalizer)
    base   = fmix32(seed + t * 0x9E3779B1)
    u_k(g) = fmix32(base ^ (g * 0x85EBCA77 + k * 0xC2B2AE3D))

and a group is offered one update for each stream k = 0 .. P - 1 with
``u_k < update_thr[g]`` and asked a read where ``u_P < read_thr[g]``; a
threshold of 0xFFFFFFFF is met by every draw. ``replay`` runs the rule
over all groups and a span of rounds (what the conservation law and the
program's own counts are held to); ``LoadCluster`` is one group: R
plain ``RawNode``s in one Python process, told its own offers round by
round and nothing of any other group.

One departure from ``ReconfCluster``, which every lockstep cell left
invisible: **a read asked while a batch is in flight waits.** The
device keeps the request (etcd's ``read_only`` queues it) and opens the
next batch with it once the one in flight is confirmed, in whatever
round that is; ``ReconfCluster._read`` drops a request it cannot serve,
which no cell that asks in every round can tell apart. Here the request
is kept until a leader serves it or ``raft.reset`` forgets it with the
rest of the read state.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from .shadow_reconf import ReconfCluster

ALWAYS = 0xFFFFFFFF
_U = np.uint32


def fmix32(x: np.ndarray) -> np.ndarray:
    """murmur3's finalizer on a uint32 array (numpy wraps as the rule
    does); the array is the caller's to lose."""
    x ^= x >> _U(16)
    x *= _U(0x85EBCA6B)
    x ^= x >> _U(13)
    x *= _U(0xC2B2AE35)
    x ^= x >> _U(16)
    return x


def round_base(seed: int, t: int) -> np.uint32:
    return fmix32(np.asarray([(seed + t * 0x9E3779B1) & ALWAYS], _U))[0]


def stream_keys(groups: np.ndarray, streams: int):
    """``g * 0x85EBCA77 + k * 0xC2B2AE3D`` for each stream k, [len(groups)]
    uint32 each: a round's draws are these under the round's base."""
    g = np.asarray(groups).astype(np.uint64)
    return [((g * 0x85EBCA77 + k * 0xC2B2AE3D) & ALWAYS).astype(_U)
            for k in range(streams)]


def offers(update_thr: np.ndarray, read_thr: np.ndarray, keys, base
           ) -> Tuple[np.ndarray, np.ndarray]:
    """(updates offered [n] int, read asked [n] bool) of one round for
    the groups whose thresholds and ``stream_keys`` these are."""
    n = np.zeros(len(update_thr), np.int64)
    for key in keys[:-1]:
        n += (fmix32(key ^ base) < update_thr) | (update_thr == ALWAYS)
    return n, (fmix32(keys[-1] ^ base) < read_thr) | (read_thr == ALWAYS)


def replay(update_thr: np.ndarray, read_thr: np.ndarray, seed: int,
           first_round: int, rounds: int, max_props: int,
           shift: int = 0) -> Tuple[np.ndarray, Dict[str, int]]:
    """The draws of rounds ``first_round`` to ``first_round + rounds -
    1`` over every group: (updates offered to each group [G] int64,
    totals by the names of the program's counts). ``shift`` draws round
    t as if it were round ``t - shift`` (a control)."""
    keys = stream_keys(np.arange(len(update_thr)), max_props + 1)
    offered = np.zeros(len(update_thr), np.int64)
    reads = active = 0
    for t in range(first_round, first_round + rounds):
        n, read = offers(update_thr, read_thr, keys,
                         round_base(seed, t - shift))
        offered += n
        reads += int(read.sum())
        active += int((read | (n > 0)).sum())
    return offered, {"offered": int(offered.sum()), "reads_asked": reads,
                     "active": active}


def group_offers(update_thr, read_thr, seed: int, group: int,
                 first_round: int, rounds: int, max_props: int,
                 shift: int = 0):
    """One group's (updates, read) round by round: two lists."""
    keys = stream_keys([group], max_props + 1)
    upd = np.asarray([update_thr[group]], _U)
    rd = np.asarray([read_thr[group]], _U)
    ns, reads = [], []
    for t in range(first_round, first_round + rounds):
        n, read = offers(upd, rd, keys, round_base(seed, t - shift))
        ns.append(int(n[0]))
        reads.append(bool(read[0]))
    return ns, reads


class LoadCluster(ReconfCluster):
    def __init__(self, num_replicas: int, **kw) -> None:
        super().__init__(num_replicas, **kw)
        self.waiting = [False] * num_replicas
        for slot, node in enumerate(self.nodes):
            self._forget_the_wait(node.raft, slot)

    def _forget_the_wait(self, r, slot: int) -> None:
        reset = r.reset

        def reset_and_forget(term):
            self.waiting[slot] = False
            reset(term)

        r.reset = reset_and_forget

    def _read(self, slot: int, asked: bool) -> None:
        """``ReconfCluster._read`` with the request kept: asked now or
        waiting since an earlier round."""
        asked = asked or self.waiting[slot]
        before = self.reads[slot].seq
        super()._read(slot, asked)
        self.waiting[slot] = asked and self.reads[slot].seq == before

    def load_round(self, offer: int, read: bool, tick: bool) -> None:
        """One round of the load plane, as this group sees it."""
        self.round(offer=offer, tick=tick, control={
            "drained": None, "transfer_to": None, "conf": None, "cut": None,
            "stall": False, "reads": read})
