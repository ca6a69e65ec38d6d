"""The reference's element kinds as rule files, and the traffic's message."""

import collections
import json

import pytest

from zbench import reference as ref
from zbench import rules, selfcheck, traffic

GRAPHS = selfcheck.process_graphs()
CASES = [(pid, v) for pid, g in GRAPHS.items() for v in range(len(g["payload_variants"]))]
with open(selfcheck.GOLDEN) as f:
    GOLDEN = json.load(f)["digests"]


@pytest.mark.parametrize("pid,variant", CASES)
def test_expected_is_the_parents(pid, variant):
    """Every process and payload variant: the same events and jobs, to the
    byte, as the reference gave before element kinds became rule files."""
    assert selfcheck.reference_digest(GRAPHS[pid], variant) == GOLDEN[f"{pid}/{variant}"]
    assert set(GOLDEN) == {f"{p}/{v}" for p, v in CASES}


def _with_kind(kind: str, start: str = "start") -> dict:
    return {
        "id": "p", "start": start,
        "nodes": {"start": {"kind": "start"}, "x": {"kind": kind}, "end": {"kind": "end"}},
        "flows": [{"id": "f0", "from": "start", "to": "x"}, {"id": "f1", "from": "x", "to": "end"}],
    }


def test_a_kind_is_a_rule_file_found_by_its_name(tmp_path, monkeypatch):
    """A file ``<kind>.py`` on the rules package's path is the kind's rule:
    nothing else is edited."""
    (tmp_path / "stamp_only.py").write_text(
        "def walk(node_id, node, payload, inputs):\n"
        "    after = {**payload, 'stamped': inputs.process}\n"
        "    return [(6, node_id, dict(payload)), (9, node_id, dict(after))], "
        "[('stamp', dict(payload), {})], after, node_id\n"
    )
    monkeypatch.setattr(rules, "__path__", [*rules.__path__, str(tmp_path)])
    want = ref.expected(_with_kind("stamp_only"), {"a": 1}, traffic.worker_result)
    assert want["events"][4:] == [
        (ref.SEQUENCE_FLOW_TAKEN, "f0", {"a": 1}),
        (6, "x", {"a": 1}), (9, "x", {"a": 1, "stamped": "p"}),
        (ref.SEQUENCE_FLOW_TAKEN, "f1", {"a": 1, "stamped": "p"}),
        (ref.END_EVENT_OCCURRED, "end", {"a": 1, "stamped": "p"}),
        (ref.ELEMENT_COMPLETING, "p", {"a": 1, "stamped": "p"}),
        (ref.ELEMENT_COMPLETED, "p", {"a": 1, "stamped": "p"}),
    ]
    assert want["jobs"] == [("stamp", {"a": 1}, {})]


@pytest.mark.parametrize("kind", ["timer_catch", "no-such-kind", "..", "os"])
def test_a_kind_without_a_rule_file_is_refused(kind):
    with pytest.raises(ValueError, match="the reference has no rule for element kind"):
        ref.expected(_with_kind(kind), {"a": 1}, traffic.worker_result)


def test_message_catch_merges_the_message_at_completion():
    graph = selfcheck.WAITING
    payload = {"orderId": 12, "orderValue": 250, "customer": "c-5"}
    message = traffic.message_for("payment-wait", payload)
    want = ref.expected(graph, payload, traffic.worker_result)
    after = {**payload, **message}
    assert [e for e in want["events"] if e[1] == "paid"] == [
        (6, "paid", payload), (7, "paid", payload), (8, "paid", message), (9, "paid", after),
    ]
    assert want["events"][-1] == (ref.ELEMENT_COMPLETED, "payment-wait", after)
    assert not want["jobs"]


def test_message_for_is_seed_stable_and_the_same_multiset_across_seeds():
    def messages(seed: int, mix: dict) -> list:
        plan = traffic.Plan(mix, {}, seed)
        creates = [plan.next() for _ in range(160)]
        return [traffic.message_for(pid, p) for _, pid, p in creates if pid == "payment-wait"]

    def multiset(ms: list) -> collections.Counter:
        return collections.Counter(json.dumps(m, sort_keys=True) for m in ms)

    alone = {"payment-wait": 1.0}
    a, b = messages(7, alone), messages(2**31 + 12, alone)
    assert a == messages(7, alone) and multiset(a) == multiset(b)
    assert len(multiset(a)) == 160 and all(set(m) == {"paid", "paymentId"} for m in a)
    # in a mix the seed picks which creates wait for a message; their
    # number is the mix's exact share
    half = {"payment-wait": 0.5, "order-process": 0.5}
    assert len(messages(7, half)) == len(messages(2**31 + 12, half)) == 80


def test_the_publish_counts_and_the_lost_publish_control():
    selfcheck.controls_of_the_publisher()
