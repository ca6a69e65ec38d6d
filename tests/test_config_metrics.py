"""Config system + metrics tests.

Reference parity: ``broker-core`` configuration tests (TOML parse, env
override, port offset) and ``util`` metrics tests (registry allocate +
prometheus dump; MetricsFileWriter flush).
"""

import pytest

from zeebe_tpu.runtime.actors import ControlledActorScheduler
from zeebe_tpu.runtime.clock import ControlledClock
from zeebe_tpu.runtime.config import BrokerCfg, load_config
from zeebe_tpu.runtime.metrics import MetricsFileWriter, MetricsRegistry


class TestConfig:
    def test_defaults(self):
        cfg = load_config(env={})
        assert cfg.network.client_port == 26501
        assert cfg.cluster.partitions == 1
        assert cfg.threads.cpu_thread_count == 2

    def test_parse_sections_camel_case(self):
        cfg = load_config(
            toml_text="""
[network]
host = "10.0.0.5"
portOffset = 2

[cluster]
nodeId = "broker-7"
initialContactPoints = ["10.0.0.1:26502"]

[[topics]]
name = "orders"
partitions = 4
replicationFactor = 3
""",
            env={},
        )
        assert cfg.network.host == "10.0.0.5"
        # port offset shifts every binding by offset * 10
        assert cfg.network.client_port == 26501 + 20
        assert cfg.network.gateway_port == 26500 + 20
        assert cfg.cluster.node_id == "broker-7"
        assert cfg.cluster.initial_contact_points == ["10.0.0.1:26502"]
        assert len(cfg.topics) == 1
        assert cfg.topics[0].partitions == 4

    def test_env_overrides_win(self):
        cfg = load_config(
            toml_text="[cluster]\nnodeId = 'from-file'\n",
            env={
                "ZEEBE_NODE_ID": "from-env",
                "ZEEBE_PORT_OFFSET": "1",
                "ZEEBE_CONTACT_POINTS": "a:1, b:2",
            },
        )
        assert cfg.cluster.node_id == "from-env"
        assert cfg.network.client_port == 26511
        assert cfg.cluster.initial_contact_points == ["a:1", "b:2"]

    @pytest.mark.parametrize(
        "toml_text, named",
        [
            ("[network]\nbogusKnob = 1\n", r"\[network\] 'bogusKnob'"),
            # removed with the per-partition drain it selected
            ("[scheduler]\nenabled = false\n", r"\[scheduler\] 'enabled'"),
        ],
    )
    def test_unknown_key_rejected(self, toml_text, named):
        with pytest.raises(ValueError, match="unknown config key " + named):
            load_config(toml_text=toml_text, env={})

    def test_removed_env_override_changes_nothing(self):
        assert load_config(env={"ZEEBE_SCHEDULER_ENABLED": "0"}) == load_config(env={})

    def test_unknown_section_rejected(self):
        with pytest.raises(ValueError, match="unknown config section"):
            load_config(toml_text="[nonsense]\nx = 1\n", env={})

    def test_default_config_file_parses(self):
        import os

        path = os.path.join(os.path.dirname(__file__), "..", "dist", "zeebe.cfg.toml")
        cfg = load_config(path=path, env={})
        assert isinstance(cfg, BrokerCfg)
        assert cfg.data.segment_size_bytes == 64 * 1024 * 1024


class TestMetrics:
    def test_counter_and_dump(self):
        reg = MetricsRegistry()
        c = reg.counter("records_processed", "Records processed", partition="0")
        c.inc()
        c.inc(2)
        out = reg.dump(now_ms=123)
        assert "# HELP zb_records_processed Records processed" in out
        assert "# TYPE zb_records_processed counter" in out
        assert 'zb_records_processed{partition="0"} 3 123' in out

    def test_same_name_labels_reuses_metric(self):
        reg = MetricsRegistry()
        a = reg.counter("x", partition="0")
        b = reg.counter("x", partition="0")
        c = reg.counter("x", partition="1")
        assert a is b and a is not c
        a.inc()
        assert b.value == 1

    def test_gauge_set(self):
        reg = MetricsRegistry()
        g = reg.gauge("backlog", "")
        g.set(17)
        assert "zb_backlog 17" in reg.dump(now_ms=1)

    def test_file_writer_flushes_atomically(self, tmp_path):
        clock = ControlledClock()
        scheduler = ControlledActorScheduler(clock=clock).start()
        reg = MetricsRegistry()
        reg.counter("up").inc()
        path = str(tmp_path / "metrics" / "zeebe.prom")
        writer = MetricsFileWriter(reg, path, scheduler, flush_period_ms=5000)
        scheduler.work_until_done()
        clock.advance(5000)
        scheduler.work_until_done()
        with open(path) as f:
            assert "zb_up 1" in f.read()


class TestGlobalEventCounters:
    """Chaos-relevant counters from layers with no broker registry in reach
    (transport, log storage, snapshot storage, raft) count into the
    process-global registry and ride along every metrics surface."""

    CHAOS_COUNTERS = (
        "raft_elections_started",
        "raft_elections_won",
        "transport_reconnects",
        "transport_pending_expired",
        "log_torn_tail_truncations",
        "snapshot_salvage_events",
    )

    def test_count_event_merges_into_any_registry_dump(self):
        from zeebe_tpu.runtime import metrics as m

        m.count_event("chaos_test_evt", "a test event")
        out = m.render_with_global(MetricsRegistry(), now_ms=1)
        assert "zb_chaos_test_evt" in out
        # the global registry itself is not duplicated
        dump = m.render_with_global(m.GLOBAL_REGISTRY, now_ms=1)
        series = [
            line for line in dump.splitlines()
            if line.startswith("zb_chaos_test_evt ")
        ]
        assert len(series) == 1

    def test_chaos_counters_exposed_through_metrics_endpoint(self):
        import urllib.request

        from zeebe_tpu.runtime import metrics as m
        from zeebe_tpu.runtime.metrics import MetricsHttpServer

        for name in self.CHAOS_COUNTERS:
            m.count_event(name, delta=0.0)  # allocate without bumping
        reg = MetricsRegistry()
        reg.counter("up").inc()
        server = MetricsHttpServer(reg, host="127.0.0.1", port=0)
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics", timeout=5
            ).read().decode()
        finally:
            server.close()
        assert "zb_up 1" in body
        for name in self.CHAOS_COUNTERS:
            assert f"zb_{name}" in body, name

    def test_raft_election_counters_count_real_elections(self, tmp_path):
        import os

        from zeebe_tpu.cluster import Raft, RaftState
        from zeebe_tpu.log import LogStream, SegmentedLogStorage
        from zeebe_tpu.runtime import metrics as m
        from zeebe_tpu.runtime.actors import ActorScheduler

        started0 = m.event_count("raft_elections_started")
        won0 = m.event_count("raft_elections_won")
        scheduler = ActorScheduler(cpu_threads=2, io_threads=2).start()
        log = LogStream(
            SegmentedLogStorage(str(tmp_path / "log")), recover_commit=False
        )
        raft = Raft(
            "m0", log, scheduler,
            storage_path=os.path.join(str(tmp_path), "raft.meta"),
        )
        try:
            raft.bootstrap({"m0": raft.address})
            import time as _t

            deadline = _t.monotonic() + 10
            while _t.monotonic() < deadline and raft.state != RaftState.LEADER:
                _t.sleep(0.02)
            assert raft.state == RaftState.LEADER
            assert m.event_count("raft_elections_started") > started0
            assert m.event_count("raft_elections_won") > won0
        finally:
            raft.close()
            scheduler.stop()

    def test_file_writer_includes_global_counters(self, tmp_path):
        from zeebe_tpu.runtime import metrics as m

        m.count_event("chaos_file_evt")
        clock = ControlledClock()
        scheduler = ControlledActorScheduler(clock=clock).start()
        reg = MetricsRegistry()
        path = str(tmp_path / "metrics" / "zeebe.prom")
        MetricsFileWriter(reg, path, scheduler, flush_period_ms=5000)
        scheduler.work_until_done()
        clock.advance(5000)
        scheduler.work_until_done()
        with open(path) as f:
            assert "zb_chaos_file_evt" in f.read()


class TestWorkflowRepositoryQueries:
    """Reference WorkflowRepositoryService: list-workflows / get-workflow
    resource requests (gateway newWorkflowRequest / newResourceRequest)."""

    def test_in_process_list_and_get(self, tmp_path):
        from zeebe_tpu.gateway import ZeebeClient
        from zeebe_tpu.models.bpmn.builder import Bpmn
        from zeebe_tpu.models.bpmn.xml import read_model
        from zeebe_tpu.runtime import Broker

        broker = Broker(num_partitions=1, data_dir=str(tmp_path / "d"))
        try:
            client = ZeebeClient(broker)
            model = (Bpmn.create_process("repo-proc").start_event()
                     .service_task("t", type="x").end_event().done())
            client.deploy_model(model)
            client.deploy_model(model)  # version 2

            all_wfs = client.list_workflows()
            assert len(all_wfs) == 2
            assert {w["version"] for w in all_wfs} == {1, 2}

            latest = client.get_workflow(bpmn_process_id="repo-proc")
            assert latest["version"] == 2
            assert read_model(latest["resource"]).processes[0].id == "repo-proc"

            v1 = client.get_workflow(bpmn_process_id="repo-proc", version=1)
            assert v1["version"] == 1
            by_key = client.get_workflow(workflow_key=v1["workflow_key"])
            assert by_key["version"] == 1
        finally:
            broker.close()

    def test_cluster_list_and_get_over_the_wire(self, tmp_path):
        import time as _t

        from zeebe_tpu.gateway.cluster_client import ClusterClient
        from zeebe_tpu.models.bpmn.builder import Bpmn
        from zeebe_tpu.runtime.cluster_broker import ClusterBroker
        from zeebe_tpu.runtime.config import BrokerCfg

        cfg = BrokerCfg()

        cfg.network.client_port = 0

        cfg.network.management_port = 0

        cfg.network.subscription_port = 0

        cfg.metrics.port = 0
        cfg.cluster.node_id = "repo-broker"
        cfg.raft.heartbeat_interval_ms = 30
        cfg.raft.election_timeout_ms = 200
        cfg.metrics.enabled = False
        broker = ClusterBroker(cfg, str(tmp_path / "b"))
        try:
            broker.open_partition(0).join(10)
            broker.bootstrap_partition(0, {})
            deadline = _t.monotonic() + 20
            while _t.monotonic() < deadline and not broker.partitions[0].is_leader:
                _t.sleep(0.02)
            client = ClusterClient([broker.client_address])
            try:
                model = (Bpmn.create_process("wire-proc").start_event()
                         .service_task("t", type="x").end_event().done())
                client.deploy_model(model)
                wfs = client.list_workflows("wire-proc")
                assert len(wfs) == 1 and wfs[0]["version"] == 1
                got = client.get_workflow(bpmn_process_id="wire-proc")
                assert got["resource"].startswith(b"<?xml")
            finally:
                client.close()
        finally:
            broker.close()
