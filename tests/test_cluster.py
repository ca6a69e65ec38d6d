"""Clustered-broker integration tests: 3 real brokers in one process.

Reference parity: ``qa/integration-tests/.../clustering/ClusteringRule``
(3 brokers from configs in temp dirs + a real client over real sockets;
BrokerLeaderChangeTest kills the leader and the cluster continues;
DeploymentClusteredTest deploys on one broker and runs instances on
partitions led by others).
"""

import time

import pytest

from zeebe_tpu.gateway.cluster_client import ClusterClient
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.runtime.cluster_broker import ClusterBroker
from zeebe_tpu.runtime.config import BrokerCfg


def wait_until(predicate, timeout=60.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


def order_process():
    return (
        Bpmn.create_process("order-process")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end")
        .done()
    )


def make_cfg(node_id, partitions=1):
    cfg = BrokerCfg()
    cfg.network.client_port = 0
    cfg.network.management_port = 0
    cfg.network.subscription_port = 0
    cfg.metrics.port = 0
    cfg.cluster.node_id = node_id
    cfg.cluster.partitions = partitions
    cfg.raft.heartbeat_interval_ms = 30
    cfg.raft.election_timeout_ms = 200
    cfg.gossip.probe_interval_ms = 50
    cfg.gossip.probe_timeout_ms = 250
    cfg.gossip.sync_interval_ms = 500
    cfg.data.snapshot_replication_period_ms = 300
    cfg.metrics.enabled = False
    return cfg


class ClusterUnderTest:
    """ClusteringRule analogue."""

    def __init__(self, tmp_path, n_brokers=3, partitions=1, engine="host"):
        self.brokers = {}
        self.partitions = partitions
        factory = None
        if engine == "tpu":
            from zeebe_tpu.tpu import TpuPartitionEngine

            def factory(pid, broker):
                return TpuPartitionEngine(
                    pid,
                    partitions,
                    repository=broker.repository,
                    clock=broker.clock,
                )

        for i in range(n_brokers):
            node_id = f"b{i}"
            self.brokers[node_id] = ClusterBroker(
                make_cfg(node_id, partitions),
                str(tmp_path / node_id),
                engine_factory=factory,
            )
        nodes = list(self.brokers.values())
        for broker in nodes[1:]:
            broker.join([nodes[0].gossip_address]).join(10)
        # every partition replicated on every broker (replication factor n)
        for pid in range(partitions):
            addrs = {
                node_id: broker.open_partition(pid).join(10)
                for node_id, broker in self.brokers.items()
            }
            for node_id, broker in self.brokers.items():
                members = {nid: a for nid, a in addrs.items() if nid != node_id}
                broker.bootstrap_partition(pid, members)

    def await_leaders(self, timeout=60):
        def all_led():
            return all(
                any(
                    pid in b.partitions and b.partitions[pid].is_leader
                    for b in self.brokers.values()
                )
                for pid in range(self.partitions)
            )

        assert wait_until(all_led, timeout), {
            nid: {pid: p.is_leader for pid, p in b.partitions.items()}
            for nid, b in self.brokers.items()
        }

    def await_members(self, timeout=60):
        """Every broker knows every other member's client address (the
        gossiped node-info a topic's placement chooses from)."""

        def known():
            return all(
                b.topology.members.get(nid, ["", 0])[0]
                for b in self.brokers.values()
                for nid in self.brokers
                if nid != b.node_id
            )

        assert wait_until(known, timeout), {
            nid: dict(b.topology.members) for nid, b in self.brokers.items()
        }

    def await_agreed_leader(self, pid, timeout=60):
        """Every replica of ``pid`` follows one leader in one term: the
        elections right after bootstrap are over (a command sent into one
        can be appended by a leader that is already deposed)."""

        def views():
            return {
                nid: (b.partitions[pid].raft.leader_id, b.partitions[pid].raft.term)
                for nid, b in self.brokers.items()
                if pid in b.partitions
            }

        def agreed():
            seen = set(views().values())
            return len(seen) == 1 and None not in next(iter(seen))

        assert wait_until(agreed, timeout), views()

    def leader_of(self, pid):
        for broker in self.brokers.values():
            server = broker.partitions.get(pid)
            if server is not None and server.is_leader:
                return broker
        return None

    def client(self, request_timeout_ms=10_000):
        return ClusterClient(
            [b.client_address for b in self.brokers.values()],
            num_partitions=self.partitions,
            request_timeout_ms=request_timeout_ms,
        )

    def close(self):
        for broker in self.brokers.values():
            broker.close()


@pytest.fixture
def cluster3(tmp_path):
    c = ClusterUnderTest(tmp_path, n_brokers=3, partitions=1)
    yield c
    c.close()


class TestClusterHappyPath:
    def test_deploy_and_complete_instance_through_the_wire(self, cluster3):
        cluster3.await_leaders()
        client = cluster3.client()
        try:
            deployed = client.deploy_model(order_process())
            assert deployed.value.deployed_workflows[0].bpmn_process_id == "order-process"

            done = []
            worker = client.open_job_worker(
                "payment-service", lambda pid, rec: done.append(rec.key) or {"paid": True}
            )
            created = client.create_instance("order-process", {"orderId": 42})
            assert created.value.workflow_instance_key > 0
            assert wait_until(lambda: len(done) == 1), done
            worker.close()
        finally:
            client.close()

    def test_all_brokers_replicate_the_log(self, cluster3):
        cluster3.await_leaders()
        client = cluster3.client()
        try:
            client.deploy_model(order_process())
            client.create_instance("order-process", partition_id=0)
            leader = cluster3.leader_of(0)
            target = leader.partitions[0].log.next_position
            assert wait_until(
                lambda: all(
                    b.partitions[0].log.next_position >= target
                    for b in cluster3.brokers.values()
                ),
            ), {
                nid: b.partitions[0].log.next_position
                for nid, b in cluster3.brokers.items()
            }
        finally:
            client.close()

    def test_topology_request_names_the_leader(self, cluster3):
        cluster3.await_leaders()
        client = cluster3.client()
        try:
            # topology is gossip-disseminated, i.e. eventually consistent —
            # poll until the reported leader matches the actual one
            def topology_converged():
                leaders = client.refresh_topology()
                leader_broker = cluster3.leader_of(0)
                return (
                    0 in leaders
                    and leader_broker is not None
                    and leaders[0].port == leader_broker.client_address.port
                )

            assert wait_until(topology_converged)
        finally:
            client.close()


class TestLeaderChange:
    def test_cluster_survives_leader_kill(self, cluster3, tmp_path):
        """BrokerLeaderChangeTest: kill the partition leader; a new leader
        takes over and clients keep working (state rebuilt by replay on the
        new leader)."""
        cluster3.await_leaders()
        client = cluster3.client()
        try:
            client.deploy_model(order_process())
            client.create_instance("order-process")

            old_leader = cluster3.leader_of(0)
            old_id = old_leader.node_id
            old_leader.close()
            del cluster3.brokers[old_id]

            assert wait_until(
                lambda: cluster3.leader_of(0) is not None
            ), "no new leader elected"

            # the new leader replayed the log: deployment + first instance
            new_leader = cluster3.leader_of(0)
            assert wait_until(
                lambda: new_leader.repository.latest("order-process") is not None,
                timeout=10,
            )

            done = []
            worker = client.open_job_worker(
                "payment-service", lambda pid, rec: done.append(rec.key)
            )
            client.create_instance("order-process")
            # both instances' jobs eventually reach the worker (the first
            # job was CREATED before the failover, rebuilt by replay)
            assert wait_until(lambda: len(done) >= 2), done
            worker.close()
        finally:
            client.close()


class TestWorkerDisconnect:
    def test_dead_worker_connection_tears_down_subscription(self, cluster3):
        """A worker whose connection dies (no clean 'remove') must not keep
        holding credits — the broker removes the subscription on connection
        close so jobs re-route to live workers."""
        cluster3.await_leaders()

        def sub_count():
            # query the CURRENT leader: a re-election installs a fresh engine
            leader = cluster3.leader_of(0)
            if leader is None or leader.partitions[0].engine is None:
                return -1
            return len(leader.partitions[0].engine.job_subscriptions)

        dead_client = cluster3.client()
        dead_client.deploy_model(order_process())
        dead_client.open_job_worker("payment-service", lambda pid, rec: None)
        assert wait_until(lambda: sub_count() >= 1, timeout=10)
        # abrupt close: transport goes away without an explicit remove
        dead_client.close()
        assert wait_until(lambda: sub_count() == 0, timeout=10)

        # a live worker now receives the work
        client = cluster3.client()
        try:
            done = []
            worker = client.open_job_worker(
                "payment-service", lambda pid, rec: done.append(rec.key) or {}
            )
            client.create_instance("order-process")
            assert wait_until(lambda: len(done) == 1), done
            worker.close()
        finally:
            client.close()


class TestTopicSubscriptions:
    def test_push_over_the_wire_with_acks(self, cluster3):
        """Records stream to the subscriber over its own connection; acks
        persist in the log (TopicSubscriptionPushProcessor parity)."""
        from zeebe_tpu.protocol.enums import ValueType

        cluster3.await_leaders()
        client = cluster3.client()
        try:
            sub = client.open_topic_subscription("audit", lambda pid, r: None)
            client.deploy_model(order_process())
            client.create_instance("order-process")
            assert wait_until(
                lambda: any(
                    r.metadata.value_type == ValueType.WORKFLOW_INSTANCE
                    for r in sub.records
                ),
            ), [r.metadata.value_type for r in sub.records]
            assert any(
                r.metadata.value_type == ValueType.DEPLOYMENT for r in sub.records
            )
            sub.close()
        finally:
            client.close()

    def test_resumes_after_leader_change(self, cluster3):
        """After a leader kill the subscriber reopens on the new leader and
        resumes from its last logged ack — no duplicate deliveries of acked
        records (modulo the unacked in-flight window, which re-delivers)."""
        cluster3.await_leaders()
        client = cluster3.client()
        try:
            sub = client.open_topic_subscription("resume", lambda pid, r: None, ack_batch=1)
            client.deploy_model(order_process())
            client.create_instance("order-process")
            assert wait_until(lambda: len(sub.records) >= 5)
            assert wait_until(lambda: sub._since_ack == 0)
            acked_through = sub.records[-1].position

            old = cluster3.leader_of(0)
            old_id = old.node_id
            old.close()
            del cluster3.brokers[old_id]
            assert wait_until(lambda: cluster3.leader_of(0) is not None)

            before = len(sub.records)
            client.create_instance("order-process")
            # acks are at-least-once: the in-flight tail (acks not yet
            # committed when the leader died) re-delivers first; wait until
            # records BEYOND the acked point (the new instance's) arrive
            assert wait_until(
                lambda: any(r.position > acked_through for r in sub.records[before:]),
            ), [r.position for r in sub.records[before:]]
            fresh = sub.records[before:]
            assert fresh[0].position > 0, "subscription rewound to log start"
            positions = [r.position for r in fresh]
            assert positions == sorted(positions)
            sub.close()
        finally:
            client.close()


class TestTopicOrchestration:
    def test_create_topic_brings_up_partitions_on_members(self, cluster3):
        """Reference TopicCreationService flow: (TOPIC, CREATE) on the system
        partition assigns partition ids, partitions come up on selected
        members, and the client is answered once every partition is led."""
        cluster3.await_leaders()
        # placement picks among the members the system leader has heard
        # of: with one not yet gossiped, a partition gets fewer replicas;
        # a create caught in a re-election is orchestrated twice (more
        # replicas) or, retried on the new leader, meets its own topic
        cluster3.await_members()
        cluster3.await_agreed_leader(0)
        # the create is answered only once both partitions are created on
        # two members, bootstrapped and led: the orchestration's own limit
        # (60 s), not the client's default 10 s, which a loaded machine
        # overruns
        client = cluster3.client(request_timeout_ms=60_000)
        try:
            created = client.create_topic("orders", partitions=2, replication_factor=2)
            pids = created.value.partition_ids
            assert len(pids) == 2
            assert all(pid >= 1 for pid in pids)

            # every new partition has a leader somewhere in the cluster
            def all_led():
                return all(
                    any(
                        pid in b.partitions and b.partitions[pid].is_leader
                        for b in cluster3.brokers.values()
                    )
                    for pid in pids
                )

            assert wait_until(all_led)

            # replication factor: each partition exists on 2 brokers
            for pid in pids:
                holders = [
                    b.node_id for b in cluster3.brokers.values() if pid in b.partitions
                ]
                assert len(holders) == 2, holders

            # the new partitions process workflow instances end to end
            # (deployment fetched on demand from the system partition)
            client.deploy_model(order_process())
            done = []
            worker = client.open_job_worker(
                "payment-service",
                lambda pid, rec: done.append(rec.key) or {},
                partitions=pids,
            )
            for pid in pids:
                client.create_instance("order-process", partition_id=pid)
            assert wait_until(lambda: len(done) == 2), done
            worker.close()
        finally:
            client.close()

    def test_duplicate_topic_rejected(self, cluster3):
        from zeebe_tpu.gateway.client import ClientException

        cluster3.await_leaders()
        client = cluster3.client()
        try:
            # The FIRST create tolerates a spurious "already exists": the
            # client's command dedup (cid) is PER BROKER, so under box
            # saturation a timed-out attempt retried across a leader
            # change appends a SECOND CREATE on the new leader, and the
            # duplicate's rejection can answer the retry even though the
            # ORIGINAL command created the topic (at-least-once across
            # failover — same semantics as the reference; the PR-8 flake
            # note traced exactly this window). Either way the topic
            # exists afterwards, which is the precondition this test
            # needs; any OTHER failure still fails the test.
            try:
                client.create_topic("dup-topic", partitions=1)
            except ClientException as e:
                assert "already exists" in str(e), e
                # the documented at-least-once window was taken: leave its
                # forensics in the test log — the flight recorder holds
                # the leadership churn that made the retry cross leaders
                from zeebe_tpu.tracing.recorder import FLIGHT

                print(
                    "[duplicate-topic tolerance branch taken] recent "
                    "flight-recorder events:\n" + FLIGHT.format_slice(40)
                )
                # the tolerance is ONLY for the duplicate-command window:
                # the topic must genuinely exist (created by our own
                # first command) — any other spurious rejection fails
                leader = cluster3.leader_of(0)
                assert leader is not None
                assert "dup-topic" in leader.partitions[0].engine.topics
            with pytest.raises(ClientException, match="already exists"):
                client.create_topic("dup-topic", partitions=1)
        finally:
            client.close()


class TestSnapshotReplication:
    def test_followers_fetch_leader_snapshots(self, tmp_path):
        """SnapshotReplicationTest parity: the leader's snapshot propagates
        to followers chunk-wise; after a leader kill the new leader recovers
        from the replicated snapshot (not a full-log replay)."""
        cluster = ClusterUnderTest(tmp_path, n_brokers=3, partitions=1)
        try:
            cluster.await_leaders()
            client = cluster.client()
            try:
                client.deploy_model(order_process())
                client.create_instance("order-process")
                leader = cluster.leader_of(0)
                leader.snapshot_all()

                def followers_have_snapshot():
                    return all(
                        b.partitions[0].snapshots.storage.list()
                        for b in cluster.brokers.values()
                    )

                assert wait_until(followers_have_snapshot), {
                    nid: len(b.partitions[0].snapshots.storage.list())
                    for nid, b in cluster.brokers.items()
                }

                # kill the leader; the successor restores from the
                # replicated snapshot and keeps serving
                old_id = leader.node_id
                leader.close()
                del cluster.brokers[old_id]
                assert wait_until(lambda: cluster.leader_of(0) is not None)
                new_leader = cluster.leader_of(0)
                assert wait_until(
                    lambda: new_leader.repository.latest("order-process") is not None,
                    timeout=10,
                )
                done = []
                worker = client.open_job_worker(
                    "payment-service", lambda pid, rec: done.append(rec.key)
                )
                assert wait_until(lambda: len(done) >= 1), done
                worker.close()
            finally:
                client.close()
        finally:
            cluster.close()


class TestSelfAssembly:
    def test_cluster_bootstraps_itself_from_config(self, tmp_path):
        """Reference bootstrap flow: brokers start from config alone (contact
        points + bootstrapExpect), gossip until the expected count is alive,
        the elector bootstraps the replicated system partition, and the
        configured [[topics]] are created — no manual partition wiring."""
        from zeebe_tpu.runtime.config import TopicCfg

        brokers = {}
        first = None
        for i in range(3):
            cfg = make_cfg(f"b{i}")
            cfg.cluster.bootstrap_expect = 3
            cfg.cluster.replication_factor = 3
            # every broker ships the same config file (reference dist model)
            cfg.topics.append(TopicCfg(name="orders", partitions=2,
                                       replication_factor=2))
            if first is not None:
                cfg.cluster.initial_contact_points = [
                    f"{first.gossip_address.host}:{first.gossip_address.port}"
                ]
            broker = ClusterBroker(cfg, str(tmp_path / f"b{i}"))
            brokers[f"b{i}"] = broker
            if first is None:
                first = broker
        try:
            # system partition comes up replicated on all three
            assert wait_until(
                lambda: any(
                    0 in b.partitions and b.partitions[0].is_leader
                    for b in brokers.values()
                ),
            )
            assert wait_until(
                lambda: all(0 in b.partitions for b in brokers.values()), 20
            )
            # the configured default topic gets orchestrated
            def topic_created():
                for b in brokers.values():
                    server = b.partitions.get(0)
                    if server and server.is_leader and server.engine:
                        t = server.engine.topics.get("orders")
                        return t is not None and t["state"] == "CREATED"
                return False

            assert wait_until(topic_created)
            # and it serves real work
            client = ClusterClient([b.client_address for b in brokers.values()])
            try:
                client.deploy_model(order_process())
                done = []
                worker = client.open_job_worker(
                    "payment-service", lambda pid, rec: done.append(rec.key) or {},
                    partitions=[1],
                )
                client.create_instance("order-process", partition_id=1)
                assert wait_until(lambda: len(done) == 1), done
                worker.close()
            finally:
                client.close()
        finally:
            for b in brokers.values():
                b.close()


class TestMultiPartition:
    def test_cross_partition_message_correlation(self, tmp_path):
        """Message published on its hash-routed partition correlates to a
        workflow instance waiting on another partition, over the
        subscription transport between leader brokers."""
        cluster = ClusterUnderTest(tmp_path, n_brokers=3, partitions=3)
        try:
            cluster.await_leaders()
            client = cluster.client()
            try:
                model = (
                    Bpmn.create_process("msg-flow")
                    .start_event()
                    .message_catch_event(
                        "wait", message_name="order-paid", correlation_key="$.orderId"
                    )
                    .end_event("end")
                    .done()
                )
                client.deploy_model(model)
                created = client.create_instance(
                    "msg-flow", {"orderId": "order-9"}, partition_id=1
                )
                instance_key = created.value.workflow_instance_key
                # wait until the subscription is actually OPEN on the
                # hash-routed message partition before publishing: with
                # the default TTL of 0 a message that finds no open
                # subscription is deleted immediately (reference
                # semantics), so publishing on a fixed sleep raced the
                # cross-partition OPEN command under CI load and the
                # instance waited forever
                from zeebe_tpu.gateway.cluster_client import _correlation_hash

                msg_partition = _correlation_hash("order-9") % 3

                def subscription_open():
                    leader = cluster.leader_of(msg_partition)
                    if leader is None:
                        return False
                    engine = leader.partitions[msg_partition].engine
                    return engine is not None and any(
                        s.message_name == "order-paid"
                        and s.correlation_key == "order-9"
                        for s in engine.message_subscriptions
                    )

                assert wait_until(subscription_open), (
                    "message subscription never opened on the message partition"
                )
                client.publish_message("order-paid", "order-9", {"paid": True})

                def instance_completed():
                    leader = cluster.leader_of(1)
                    if leader is None or leader.partitions[1].engine is None:
                        return False
                    return (
                        leader.partitions[1].engine.element_instances.get(instance_key)
                        is None
                    )

                assert wait_until(instance_completed)
            finally:
                client.close()
        finally:
            cluster.close()


@pytest.mark.slow
class TestTpuClusterServing:
    """VERDICT round-2 bar: the TPU device engine is the cluster serving
    path — installed per partition on raft leadership
    (``PartitionInstallService.java:106-291`` analogue), with device
    snapshots replicating to followers and restore+replay on failover.

    Tier-2 (``pytest -m slow``): 3-broker clusters serving from the device
    kernel pay multi-ten-second cold XLA compiles PER LEADERSHIP INSTALL;
    on a shared-CPU container that exceeds the in-test client budgets and
    the whole class runs 200s+ — too heavy (and too machine-sensitive) for
    the tier-1 wall budget."""

    def test_device_partitions_serve_and_failover(self, tmp_path):
        cluster = ClusterUnderTest(tmp_path, n_brokers=3, partitions=1, engine="tpu")
        try:
            cluster.await_leaders()
            from zeebe_tpu.tpu import TpuPartitionEngine

            leader = cluster.leader_of(0)
            assert isinstance(leader.partitions[0].engine, TpuPartitionEngine)

            client = cluster.client()
            try:
                client.deploy_model(order_process())
                done = []
                worker = client.open_job_worker(
                    "payment-service", lambda pid, rec: done.append(rec.key)
                )
                client.create_instance("order-process", {"orderId": 1})
                assert wait_until(lambda: len(done) >= 1), done

                # checkpoint on the leader; followers fetch the device
                # snapshot chunk-wise (it must decode as the device envelope)
                leader.snapshot_all()

                def followers_have_snapshot():
                    return all(
                        b.partitions[0].snapshots.storage.list()
                        for b in cluster.brokers.values()
                    )

                assert wait_until(followers_have_snapshot)

                old_id = leader.node_id
                leader.close()
                del cluster.brokers[old_id]
                assert wait_until(lambda: cluster.leader_of(0) is not None)
                new_leader = cluster.leader_of(0)
                assert isinstance(new_leader.partitions[0].engine, TpuPartitionEngine)

                # the recovered device engine keeps serving: new instance
                # completes end-to-end (worker re-subscribes internally via
                # the cluster client's reconnect)
                client.create_instance("order-process", {"orderId": 2})
                assert wait_until(lambda: len(done) >= 2), done
                worker.close()
            finally:
                client.close()
        finally:
            cluster.close()

    def test_multi_partition_device_cluster(self, tmp_path):
        """Two device-backed partitions serving independently (the DP
        sharding axis of SURVEY §2: partitions are the shards)."""
        cluster = ClusterUnderTest(tmp_path, n_brokers=2, partitions=2, engine="tpu")
        try:
            cluster.await_leaders()
            from zeebe_tpu.tpu import TpuPartitionEngine

            for pid in (0, 1):
                leader = cluster.leader_of(pid)
                assert isinstance(leader.partitions[pid].engine, TpuPartitionEngine)
            client = cluster.client()
            try:
                client.deploy_model(order_process())
                done = []
                worker = client.open_job_worker(
                    "payment-service", lambda pid, rec: done.append((pid, rec.key))
                )
                for i in range(6):  # round-robins over both partitions
                    client.create_instance("order-process", {"orderId": i})
                assert wait_until(lambda: len(done) >= 6), done
                assert {pid for pid, _ in done} == {0, 1}
                worker.close()
            finally:
                client.close()
        finally:
            cluster.close()

    def test_mixed_host_and_device_workflows_across_failover(self, tmp_path):
        """A device-eligible workflow and a host-demoted one (message
        receive) served by the same device partition, surviving a leader
        kill (VERDICT round-2 item 10)."""
        cluster = ClusterUnderTest(tmp_path, n_brokers=3, partitions=1, engine="tpu")
        try:
            cluster.await_leaders()
            client = cluster.client()
            try:
                client.deploy_model(order_process())
                client.deploy_model(
                    Bpmn.create_process("await-payment")
                    .start_event()
                    .receive_task(
                        "wait", message_name="paid", correlation_key="$.oid"
                    )
                    .end_event()
                    .done()
                )
                done = []
                worker = client.open_job_worker(
                    "payment-service",
                    lambda pid, rec: done.append(rec.key),
                    timeout_ms=8_000,
                )
                client.create_instance("order-process", {"orderId": 1})
                client.create_instance("await-payment", {"oid": "a-1"})
                assert wait_until(lambda: len(done) >= 1), done

                old = cluster.leader_of(0)
                old.close()
                del cluster.brokers[old.node_id]
                assert wait_until(lambda: cluster.leader_of(0) is not None)

                # device workflow still serves...
                client.create_instance("order-process", {"orderId": 2})
                assert wait_until(lambda: len(done) >= 2), done
                # ...and the host-demoted instance still correlates
                client.publish_message("paid", "a-1", {"ok": True})

                def host_done():
                    leader = cluster.leader_of(0)
                    records = [
                        r for r in leader.partitions[0].log.reader(0)
                        if getattr(r.value, "bpmn_process_id", "") == "await-payment"
                        and r.metadata.intent == 9  # ELEMENT_COMPLETED
                    ]
                    return bool(records)

                assert wait_until(host_done)
                worker.close()
            finally:
                client.close()
        finally:
            cluster.close()

    def test_device_snapshot_under_load(self, tmp_path):
        """Checkpointing while instances are in flight (jobs outstanding)
        must capture a restorable state: kill the leader mid-stream and
        the successor finishes the backlog."""
        cluster = ClusterUnderTest(tmp_path, n_brokers=3, partitions=1, engine="tpu")
        try:
            cluster.await_leaders()
            client = cluster.client()
            try:
                client.deploy_model(order_process())
                done = []

                def handler(pid, rec):
                    done.append(rec.key)
                    return {"paid": True}

                # short job timeout: a job in flight when the leader dies
                # must re-activate within the test window (at-least-once)
                worker = client.open_job_worker(
                    "payment-service", handler, timeout_ms=8_000
                )
                for i in range(8):
                    client.create_instance("order-process", {"orderId": i})
                # snapshot while some jobs are still outstanding
                leader = cluster.leader_of(0)
                leader.snapshot_all()
                assert wait_until(
                    lambda: all(
                        b.partitions[0].snapshots.storage.list()
                        for b in cluster.brokers.values()
                    ),
                )
                old_id = leader.node_id
                leader.close()
                del cluster.brokers[old_id]
                assert wait_until(lambda: cluster.leader_of(0) is not None)
                assert wait_until(lambda: len(done) >= 8, timeout=40), len(done)
                worker.close()
            finally:
                client.close()
        finally:
            cluster.close()

    def test_fresh_worker_after_failover_gets_backlog(self, tmp_path):
        """A worker that does NOT re-subscribe across the failover: jobs
        created before the leader died are activated for a NEW worker that
        first connects to the successor (backlog activation on subscribe —
        reference ActivateJobStreamProcessor reads the log from the
        start)."""
        cluster = ClusterUnderTest(tmp_path, n_brokers=3, partitions=1, engine="tpu")
        try:
            cluster.await_leaders()
            client = cluster.client()
            try:
                client.deploy_model(order_process())
                # no worker yet: jobs pile up as CREATED
                for i in range(3):
                    client.create_instance("order-process", {"orderId": i})

                def jobs_created():
                    leader = cluster.leader_of(0)
                    return (
                        sum(
                            1 for r in leader.partitions[0].log.reader(0)
                            if r.metadata.value_type == 0  # JOB
                            and r.metadata.intent == 1  # CREATED
                        )
                        >= 3
                    )

                assert wait_until(jobs_created)
                old = cluster.leader_of(0)
                old.close()
                del cluster.brokers[old.node_id]
                assert wait_until(lambda: cluster.leader_of(0) is not None)
            finally:
                client.close()
            # a FRESH client+worker connects only after the failover
            client2 = cluster.client()
            try:
                done = []
                worker = client2.open_job_worker(
                    "payment-service", lambda pid, rec: done.append(rec.key)
                )
                assert wait_until(lambda: len(done) >= 3), done
                worker.close()
            finally:
                client2.close()
        finally:
            cluster.close()


@pytest.mark.slow
class TestTpuClusterDeadlines:
    """Tier-2 with TestTpuClusterServing (same device-engine cluster
    bring-up cost). Round-4 regression (deadline sweeps dead on clustered TPU
    partitions): the broker tick must fire job timeouts, timer events and
    host-oracle deadlines on a TPU-backed partition — the async device
    probe (``tpu/engine.deadlines_due_probe``) gates the expensive device
    column sweeps, while host-oracle deadlines are swept unconditionally
    every tick. Reference periodic jobs: ``JobTimeOutStreamProcessor``,
    ``MessageTimeToLiveChecker`` (broker-core job/message processors)."""

    def _cluster(self, tmp_path):
        return ClusterUnderTest(tmp_path, n_brokers=3, partitions=1, engine="tpu")

    def test_device_timer_fires_through_the_tick(self, tmp_path):
        cluster = self._cluster(tmp_path)
        try:
            cluster.await_leaders()
            from zeebe_tpu.tpu import TpuPartitionEngine

            assert isinstance(
                cluster.leader_of(0).partitions[0].engine, TpuPartitionEngine
            )
            client = cluster.client()
            try:
                model = (
                    Bpmn.create_process("timer-flow")
                    .start_event()
                    .timer_catch_event("wait", duration_ms=700)
                    .service_task("after", type="timer-done")
                    .end_event("end")
                    .done()
                )
                # device-eligible: the timer lives in the DEVICE timer
                # table; its TRIGGER only fires if the probe-gated sweep runs
                from zeebe_tpu.models.transform import transform_model
                from zeebe_tpu.tpu.graph import check_device_compatible

                wf = transform_model(model)[0]
                assert check_device_compatible(wf) is None

                client.deploy_model(model)
                done = []
                worker = client.open_job_worker(
                    "timer-done", lambda pid, rec: done.append(rec.key) or {}
                )
                client.create_instance("timer-flow", {})
                assert wait_until(lambda: len(done) == 1, timeout=30), done
                worker.close()
            finally:
                client.close()
        finally:
            cluster.close()

    def test_device_job_timeout_reactivates_through_the_tick(self, tmp_path):
        from zeebe_tpu.gateway.cluster_client import RemoteJobWorker

        class NoCompleteWorker(RemoteJobWorker):
            """Takes pushes but never completes/fails: the job can only
            come back via a server-side TIME_OUT sweep."""

            def _on_record(self, partition, record, epoch=-1):
                self.handled.append(record)
                self._return_credit(partition)

        cluster = self._cluster(tmp_path)
        try:
            cluster.await_leaders()
            client = cluster.client()
            try:
                client.deploy_model(order_process())
                worker = NoCompleteWorker(
                    client, "payment-service", handler=None,
                    worker_name="sloth", credits=4, timeout_ms=800,
                    partitions=[0],
                )
                client.create_instance("order-process", {"orderId": 1})
                # 1st push = activation; 2nd push of the SAME job key can
                # only happen after the tick swept its deadline (TIME_OUT)
                assert wait_until(
                    lambda: len(worker.handled) >= 2, timeout=30
                ), [r.key for r in worker.handled]
                keys = {r.key for r in worker.handled}
                assert len(keys) == 1, keys
                worker.close()
            finally:
                client.close()
        finally:
            cluster.close()

    def test_host_demoted_timer_fires_every_tick_unconditionally(self, tmp_path):
        """Host-oracle deadlines (device-INELIGIBLE workflows inside a TPU
        partition) must fire even when no device-side deadline is ever due
        — the round-4 bug gated them behind the device probe."""
        cluster = self._cluster(tmp_path)
        try:
            cluster.await_leaders()
            client = cluster.client()
            try:
                builder = (
                    Bpmn.create_process("host-timer-flow")
                    .start_event()
                    .timer_catch_event("wait", duration_ms=700)
                    .service_task("after", type="host-timer-done")
                )
                sub = builder.sub_process(
                    "each", multi_instance={"input_collection": "$.items",
                                            "input_element": "item"}
                )
                sub.start_event("s").end_event("e")
                model = sub.embedded_done().end_event("end").done()

                from zeebe_tpu.models.transform import transform_model
                from zeebe_tpu.tpu.graph import check_device_compatible

                wf = transform_model(model)[0]
                assert check_device_compatible(wf) is not None  # host-demoted

                client.deploy_model(model)
                done = []
                worker = client.open_job_worker(
                    "host-timer-done", lambda pid, rec: done.append(rec.key) or {}
                )
                client.create_instance("host-timer-flow", {"items": []})
                assert wait_until(lambda: len(done) == 1, timeout=30), done
                worker.close()
            finally:
                client.close()
        finally:
            cluster.close()
