"""chip_smoke.py rehearsed on the CPU, and the fail-open repair it leans
on: a device batch that raises still delivers, but never in silence.

The chip itself is met only through ``python chip_smoke.py`` under the
chip tool; here the same script runs in-process at a few thousand
filters (``--rehearse``), and refuses a CPU without that flag.
"""

import asyncio
import json
import logging

import pytest

import chip_smoke
from emqx_tpu import faultinject
from emqx_tpu.client import Client
from emqx_tpu.config import Config
from emqx_tpu.faultinject import FaultInjector
from emqx_tpu.node import BrokerNode


def test_rehearse_exits_zero_with_the_contract_last_line(capsys):
    import jax

    assert chip_smoke.main(["--rehearse", "--seed", "1"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    dev = jax.devices()[0]
    # the last line is exactly the contract object, truthfully a CPU
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": dev.device_kind,
        "count": len(jax.devices())}}
    phases = [json.loads(ln) for ln in lines[:-1]]
    assert [p["phase"] for p in phases] == [
        "table", "mirror", "warmup", "deliveries", "counters", "parity"]
    by = {p["phase"]: p for p in phases}
    assert by["table"]["config_set"]["tpu.bypass_rate"] == 0.0
    assert by["mirror"]["table_kind"] == "native"
    assert by["mirror"]["device_bytes_in_use"] is None   # CPU: no stats
    assert by["deliveries"]["expected"] > 0
    assert by["deliveries"]["missing"] == by["deliveries"]["extra"] == 0
    d = by["counters"]["delta"]
    assert d["tpu.match.hint_served"] == by["counters"]["publishes"]
    assert d["broker.match.cpu_fallback"] == d["tpu.match.bypass"] == 0
    assert by["parity"]["mismatches"] == 0


@pytest.mark.parametrize("argv,says", [
    ([], "platform 'cpu'"),                       # no chip, no --rehearse
    (["--seed", "3"], "platform 'cpu'"),
    (["--rehearse", "--chips", "4"], "exactly four devices"),  # 8 here
])
def test_refuses_before_building_anything(monkeypatch, capsys, argv, says):
    def boom(*_a, **_k):
        raise AssertionError("the smoke started building on a refusal")

    monkeypatch.setattr(chip_smoke, "run", boom)
    assert chip_smoke.main(argv) != 0
    io = capsys.readouterr()
    assert io.out == ""            # no result line, no phase line
    assert says in io.err


def test_raising_device_batch_counts_cpu_fallback_and_still_delivers(caplog):
    """The default serve loop's fail-open path (``_serve_batch``): every
    device batch raises at the ``match.dispatch`` seam, the host trie
    delivers every message, ``broker.match.cpu_fallback`` counts the
    waiters, and the error is logged at WARNING once, not per batch."""

    async def main():
        cfg = Config(file_text='listeners.tcp.default.bind = "127.0.0.1:0"\n')
        cfg.put("tpu.enable", True)
        cfg.put("tpu.bypass_rate", 0.0)
        node = BrokerNode(cfg)
        await node.start()
        port = node.listeners.all()[0].port
        ms = node.match_service
        m = node.observed.metrics
        sub = Client(clientid="s", port=port)
        pub = Client(clientid="p", port=port)
        try:
            await sub.connect()
            await sub.subscribe("room/+/temp", qos=1)
            await pub.connect()
            for _ in range(600):
                if ms.ready and ms._seen_epoch == node.broker.router.epoch \
                        and ms.dev.epoch == ms.inc.epoch:
                    break
                await asyncio.sleep(0.05)
            assert ms.ready
            batches0 = m.get("tpu.match.batches")
            inj = faultinject.install(FaultInjector([
                {"point": "match.dispatch", "action": "raise", "times": 0},
            ], seed=1))
            try:
                for i in range(12):
                    await pub.publish(f"room/{i}/temp", b"%d" % i, qos=1)
                got = sorted([int((await sub.recv(10.0)).payload)
                              for _ in range(12)])
            finally:
                faultinject.uninstall()
            assert got == list(range(12))            # fail-open delivers
            assert inj.fired["match.dispatch"] >= 12   # serial publishes
            assert m.get("broker.match.cpu_fallback") >= 12
            assert m.get("tpu.match.batches") == batches0
            assert m.get("tpu.match.hint_served") == 0
        finally:
            await sub.close()
            await pub.close()
            await node.stop()

    with caplog.at_level(logging.WARNING,
                         logger="emqx_tpu.broker.match_service"):
        asyncio.run(main())
    warned = [r for r in caplog.records
              if r.name == "emqx_tpu.broker.match_service"
              and "device batch failed" in r.getMessage()]
    assert len(warned) == 1, [r.getMessage() for r in caplog.records]
    assert warned[0].levelno == logging.WARNING and warned[0].exc_info
