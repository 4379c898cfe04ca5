"""Must TRIP registry-drift on all nine surfaces (checked against the
real registries in observe/metrics.py / config.py / faultinject.py /
broker/hooks.py / observe/hist.py / observe/flightrec.py)."""


def f(metrics, cfg, alarms, hooks, _injector):
    metrics.inc("tpu.match.not_a_real_metric")
    metrics.get("tpu.match.not_a_real_read")
    cfg.get("mqtt.not_a_real_key")
    _injector.check("bogus.point")
    alarms.deactivate("never_activated_alarm")
    hooks.run("message.dropped", (None, "not_a_real_reason"))


def g(hooks):
    hooks.add("client.not_a_real_point", lambda: None)


def h(hists, flightrec):
    hists.hist("obs.stage.not_a_real_stage")
    flightrec.dump("not_a_declared_reason")
    stage_span("not_a_real_stage", hists, flightrec.ring("x"))
