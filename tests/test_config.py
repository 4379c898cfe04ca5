"""Typed layered config — emqx_config/emqx_schema/hocon parity
(SURVEY.md §5.6)."""

import pytest

from emqx_tpu.config import Config, parse_hocon, duration, bytesize


def test_value_parsers():
    assert duration("15s") == 15.0
    assert duration("2m") == 120.0
    assert duration("100ms") == 0.1
    assert bytesize("1MB") == 1 << 20
    assert bytesize("64KB") == 64 << 10
    with pytest.raises(ValueError):
        duration("abc")


def test_hocon_subset():
    text = """
    # comment
    node.name = "n1@host"     // trailing comment
    mqtt {
      max_packet_size = 2MB
      max_inflight = 64
      retain_available = false
    }
    broker.shared_subscription_strategy = round_robin
    listeners.tcp.default { bind = "127.0.0.1:1883" }
    tags = [a, "b c", 3]
    """
    d = parse_hocon(text)
    assert d["node"]["name"] == "n1@host"
    assert d["mqtt"]["max_packet_size"] == "2MB"
    assert d["mqtt"]["max_inflight"] == 64
    assert d["mqtt"]["retain_available"] is False
    assert d["broker"]["shared_subscription_strategy"] == "round_robin"
    assert d["listeners"]["tcp"]["default"]["bind"] == "127.0.0.1:1883"
    assert d["tags"] == ["a", "b c", 3]


def test_layering_defaults_file_env():
    cfg = Config(
        file_text="mqtt.max_inflight = 64\nmqtt.session_expiry_interval = 1h",
        env={"EMQX_MQTT__MAX_INFLIGHT": "128", "UNRELATED": "x"},
    )
    assert cfg.get("mqtt.max_inflight") == 128            # env wins
    assert cfg.get("mqtt.session_expiry_interval") == 3600.0  # file
    assert cfg.get("mqtt.max_qos_allowed") == 2           # default


def test_schema_rejects_unknown_and_invalid():
    with pytest.raises(ValueError):
        Config(file_text="mqtt.not_a_key = 1", env={})
    with pytest.raises(ValueError):
        Config(file_text="mqtt.max_qos_allowed = 7", env={})
    cfg = Config(env={})
    with pytest.raises(ValueError):
        cfg.put("broker.shared_subscription_strategy", "bogus")


def test_zone_overrides():
    cfg = Config(
        file_text="""
        mqtt.max_inflight = 32
        zones.external.mqtt.max_inflight = 8
        """,
        env={},
    )
    assert cfg.zone(None).get("mqtt.max_inflight") == 32
    assert cfg.zone("external").get("mqtt.max_inflight") == 8
    assert cfg.zone("external").get("mqtt.max_qos_allowed") == 2


def test_hot_update_handler_two_phase():
    cfg = Config(env={})
    seen = []
    cfg.on_update("tpu.", lambda p, old, new: seen.append((p, old, new)))
    cfg.put("tpu.batch_size", 8192)
    assert seen == [("tpu.batch_size", 2048, 8192)]
    assert cfg.get("tpu.batch_size") == 8192

    def boom(p, old, new):
        raise RuntimeError("refuse")

    cfg.on_update("tpu.", boom)
    with pytest.raises(RuntimeError):
        cfg.put("tpu.batch_size", 1024)
    assert cfg.get("tpu.batch_size") == 8192  # rolled back


def test_duration_and_size_coercion_via_env():
    cfg = Config(env={"EMQX_MQTT__MAX_PACKET_SIZE": "2MB",
                      "EMQX_TPU__BATCH_DEADLINE": "500ms"})
    assert cfg.get("mqtt.max_packet_size") == 2 << 20
    assert cfg.get("tpu.batch_deadline") == 0.5


def test_schema_clamps_multichip_autotune_keys():
    """ISSUE 20 registry hygiene: the autotune keys validate their
    documented ranges; the readback-mode keys are gone (ISSUE 32) and
    rejected as every unknown key is."""
    cfg = Config(env={})
    assert cfg.get("match.multichip.ep.autotune.enable") is False
    cfg.put("match.multichip.ep.autotune.enable", True)
    cfg.put("match.multichip.ep.autotune.grow_threshold", 0.1)
    cfg.put("match.multichip.ep.autotune.shrink_threshold", 0.0)
    cfg.put("match.multichip.ep.autotune.max_cap_class", 8)
    cfg.put("match.multichip.ep.autotune.max_moved_roots", 0)
    with pytest.raises(ValueError):
        cfg.put("match.multichip.ep.autotune.grow_threshold", 0.0)
    with pytest.raises(ValueError):
        cfg.put("match.multichip.ep.autotune.grow_threshold", 1.5)
    with pytest.raises(ValueError):
        cfg.put("match.multichip.ep.autotune.shrink_threshold", -0.1)
    with pytest.raises(ValueError):
        cfg.put("match.multichip.ep.autotune.max_cap_class", 9)
    with pytest.raises(ValueError):
        cfg.put("match.multichip.ep.autotune.max_cap_class", -1)
    with pytest.raises(ValueError):
        cfg.put("match.multichip.ep.autotune.max_moved_roots", 5000)
    with pytest.raises(ValueError, match="unknown config key"):
        cfg.put("match.readback.mode", "ragged")
