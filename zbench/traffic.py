"""The one general traffic generator's plan: which create goes out when,
with what payload, and what the traffic answers an instance with besides
(a worker's result, a published message). Everything is drawn from the
seed, and every seed gets the same multiset of processes, payload values,
messages and arrival gaps in another order, so that the seed changes the
order of the work and not its amount. Standard library only: the child
processes import it."""

from __future__ import annotations

import math
import random
from typing import Callable, NamedTuple

ORDER_VALUES = (40, 99, 100, 250, 75, 180, 20, 1000)
BLOCK = 20  # creates per shuffled block; shares are exact per block


def mix_block(mix: dict) -> list:
    """The process ids of one block, in the mix's exact shares."""
    counts = {pid: round(share * BLOCK) for pid, share in mix.items()}
    if sum(counts.values()) != BLOCK or min(counts.values()) < 1:
        raise ValueError(f"mix shares must be multiples of 1/{BLOCK}: {mix}")
    return [pid for pid in sorted(counts) for _ in range(counts[pid])]


def worker_result(job_payload: dict) -> dict:
    """What a worker answers a job with (the reference derives the same)."""
    return {"done": True, "receipt": (int(job_payload.get("orderId", 0)) * 7919 + 13) % 100003}


def message_for(process_id: str, create_payload: dict) -> dict:
    """The payload of the one message published for an instance of
    ``process_id`` (the reference derives the same): a function of the
    create's ``orderId``, its place in the sequence, so every seed gets the
    same multiset of messages, in the order the seed gives the creates.
    Scalars only, and no string unique to an instance."""
    order = int(create_payload.get("orderId", 0))
    return {"paid": True, "paymentId": (order * 104729 + 17) % 1000003}


class Inputs(NamedTuple):
    """What the traffic feeds an instance besides its create: the worker's
    answer to a job and the message published for it, with the instance's
    process id and create payload. The reference hands it to a rule file,
    which calls what it needs."""

    worker_result: Callable
    message_for: Callable
    process: str
    create: dict


class Plan:
    """An endless seeded sequence of creates: (seq, process id, payload)."""

    def __init__(self, mix: dict, variants: dict, seed: int):
        self.block = mix_block(mix)
        self.variants = variants  # process id -> list of payload overrides
        self.seed = seed
        self.rng = random.Random(seed)
        self.seq = 0
        self._pending: list = []
        self._variant_turn = {pid: 0 for pid in mix}

    def next(self, pid: str | None = None) -> tuple:
        """The next create of the sequence; with ``pid``, one of that
        process outside the blocks (the warm-up's)."""
        if pid is None:
            if not self._pending:
                self._pending = list(self.block)
                self.rng.shuffle(self._pending)
            pid = self._pending.pop()
        seq = self.seq
        self.seq += 1
        payload = {
            "orderId": seq,
            "orderValue": ORDER_VALUES[(seq * 5 + self.seed) % len(ORDER_VALUES)],
            "customer": f"c-{seq % 7}",
        }
        turn = self._variant_turn[pid]
        self._variant_turn[pid] = turn + 1
        options = self.variants.get(pid) or [{}]
        payload.update(options[turn % len(options)])
        return seq, pid, payload


def arrival_offsets(rate_per_s: float, seconds: float, seed: int,
                    burst: dict | None = None) -> list:
    """Due times (s from the first) of an open loop: the gaps are the
    quantiles of the exponential distribution at that rate, the same for
    every seed, in an order shuffled by the seed. With ``burst``
    (``factor``, ``burst_s``, ``period_s``) the same arrivals are warped in
    time: the first ``burst_s`` of every ``period_s`` run at ``factor``
    times the rate of the rest, the mean rate unchanged."""
    n = max(1, round(rate_per_s * seconds))
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = seconds / sum(gaps)
    random.Random(seed ^ 0x5EED).shuffle(gaps)
    out, t = [], 0.0
    for g in gaps:
        out.append(t)
        t += g * scale
    if not burst:
        return out
    factor, on, period = burst["factor"], burst["burst_s"], burst["period_s"]
    # share of a period's arrivals that fall into its burst
    in_burst = factor * on / (factor * on + (period - on))

    def warp(t: float) -> float:
        k, u = divmod(t, period)
        u /= period  # the share of this period's arrivals that came before
        if u < in_burst:
            return k * period + on * u / in_burst
        return k * period + on + (period - on) * (u - in_burst) / (1.0 - in_burst)

    return [warp(t) for t in out]
