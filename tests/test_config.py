"""Config tests (openr/config/tests/ConfigTest.cpp equivalents): JSON load,
defaults, validation, area regex matching, feature predicates."""

import json

import pytest

from openr_tpu.config import Config, OpenrConfig
from openr_tpu.types import PrefixForwardingAlgorithm, PrefixForwardingType


def test_defaults_match_reference():
    cfg = Config.from_dict({"node_name": "n1"})
    c = cfg.config
    assert c.openr_ctrl_port == 2018
    assert c.kvstore_config.key_ttl_ms == 300_000
    assert c.kvstore_config.sync_interval_s == 60
    assert c.spark_config.hello_time_s == 20.0
    assert c.spark_config.keepalive_time_s == 2.0
    assert c.spark_config.hold_time_s == 10.0
    assert c.spark_config.graceful_restart_time_s == 30.0
    assert c.spark_config.fastinit_hello_time_ms == 500.0
    assert c.link_monitor_config.linkflap_initial_backoff_ms == 60_000
    assert c.link_monitor_config.linkflap_max_backoff_ms == 300_000
    assert c.watchdog_config.thread_timeout_s == 300
    assert c.watchdog_config.max_memory_mb == 800
    assert c.prefix_forwarding_type == PrefixForwardingType.IP
    assert (
        c.prefix_forwarding_algorithm == PrefixForwardingAlgorithm.SP_ECMP
    )


def test_node_name_required():
    with pytest.raises(ValueError):
        Config.from_dict({})


@pytest.mark.parametrize(
    "extra",
    [
        {"not_a_field": 1},
        # a key that existed until PR 31 is rejected like any other
        {"stream_config": {"shared_encode": False}},
    ],
    ids=["top_level", "stream_config.shared_encode"],
)
def test_unknown_field_rejected(extra):
    with pytest.raises(ValueError, match="unknown config field"):
        Config.from_dict({"node_name": "n1", **extra})


def test_load_file(tmp_path):
    path = tmp_path / "openr.json"
    path.write_text(
        json.dumps(
            {
                "node_name": "node-7",
                "domain": "test",
                "openr_ctrl_port": 3018,
                "enable_segment_routing": True,
                "kvstore_config": {"key_ttl_ms": 60000},
                "spark_config": {"hello_time_s": 5},
                "areas": [
                    {
                        "area_id": "pod-1",
                        "interface_regexes": ["eth[0-9]+"],
                        "neighbor_regexes": ["rsw.*"],
                    }
                ],
            }
        )
    )
    cfg = Config.load_file(str(path))
    assert cfg.node_name == "node-7"
    assert cfg.config.openr_ctrl_port == 3018
    assert cfg.is_segment_routing_enabled()
    assert cfg.config.kvstore_config.key_ttl_ms == 60000
    assert cfg.config.kvstore_config.sync_interval_s == 60  # default kept
    assert cfg.config.spark_config.hello_time_s == 5


def test_area_matching():
    cfg = Config.from_dict(
        {
            "node_name": "n1",
            "areas": [
                {
                    "area_id": "spine",
                    "interface_regexes": [],
                    "neighbor_regexes": ["ssw.*"],
                },
                {
                    "area_id": "rack",
                    "interface_regexes": ["eth[0-9]"],
                    "neighbor_regexes": [],
                },
            ],
        }
    )
    assert cfg.get_area_ids() == ["spine", "rack"]
    assert cfg.get_area_for(neighbor_name="ssw001") == "spine"
    assert cfg.get_area_for(if_name="eth0") == "rack"
    assert cfg.get_area_for(if_name="po1", neighbor_name="fsw1") is None


def test_no_areas_default():
    cfg = Config.from_dict({"node_name": "n1"})
    assert cfg.get_area_ids() == ["0"]
    assert cfg.get_area_for(if_name="anything") == "0"
