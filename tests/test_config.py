"""Config validation tests (semantics of ref openr/config/tests/ConfigTest.cpp)."""

import pytest

from openr_tpu.config import (
    AreaConfig,
    Config,
    ConfigError,
    OpenrConfig,
)


def _base(**kw) -> OpenrConfig:
    return OpenrConfig(node_name="node1", **kw)


def test_valid_default_config():
    cfg = Config(_base())
    assert cfg.node_name == "node1"
    assert cfg.area_ids() == ["0"]


def test_node_name_required():
    with pytest.raises(ConfigError):
        Config(OpenrConfig())
    with pytest.raises(ConfigError):
        Config(OpenrConfig(node_name="bad name"))


def test_duplicate_areas_rejected():
    with pytest.raises(ConfigError):
        Config(_base(areas=[AreaConfig("a"), AreaConfig("a")]))


def test_spark_timer_validation():
    cfg = _base()
    cfg.spark_config.hold_time_s = 1.0
    cfg.spark_config.keepalive_time_s = 2.0
    with pytest.raises(ConfigError):
        Config(cfg)


def test_decision_debounce_validation():
    cfg = _base()
    cfg.decision_config.debounce_min_ms = 500
    cfg.decision_config.debounce_max_ms = 100
    with pytest.raises(ConfigError):
        Config(cfg)


def test_solver_backend_validation():
    cfg = _base()
    cfg.decision_config.solver_backend = "gpu"
    with pytest.raises(ConfigError):
        Config(cfg)


def test_area_matchers():
    cfg = _base(
        areas=[
            AreaConfig(
                area_id="spine",
                neighbor_regexes=["ssw.*"],
                include_interface_regexes=["eth.*"],
                exclude_interface_regexes=["eth99"],
            ),
            AreaConfig(area_id="pod", neighbor_regexes=["rsw.*"],
                       include_interface_regexes=[".*"]),
        ]
    )
    c = Config(cfg)
    assert c.match_neighbor_area("ssw001", "eth0") == "spine"
    assert c.match_neighbor_area("ssw001", "eth99") is None  # excluded in spine
    assert c.match_neighbor_area("rsw001", "po1") == "pod"
    assert c.match_neighbor_area("unknown", "xe0") is None


def test_json_roundtrip():
    c = Config(_base())
    c2 = Config.from_json(c.dump_json())
    assert c2.node_name == "node1"
    assert c2.raw.spark_config.hold_time_s == c.raw.spark_config.hold_time_s


def test_bad_json():
    with pytest.raises(ConfigError):
        Config.from_json("{not json")


def test_a_file_that_names_a_deleted_knob_loads():
    """`decision_config.streaming_pipeline` went with its path (PR 46):
    a deployed file that still names it loads — the loader drops the key
    — and runs the inline path, which is what "off" always meant."""
    import json

    plain = json.loads(Config(_base()).dump_json())
    plain["decision_config"]["streaming_pipeline"] = True
    cfg = Config.from_json(json.dumps(plain))
    assert cfg.node_name == "node1"
    assert not hasattr(cfg.raw.decision_config, "streaming_pipeline")
    assert cfg.raw.decision_config == Config(_base()).raw.decision_config
