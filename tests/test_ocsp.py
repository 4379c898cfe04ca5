"""OCSP stapling cache against a mocked responder (emqx_ocsp_cache
analog).  A throwaway CA + server cert are generated
in-test; the responder is an injected fetch callable building real
RFC 6960 DER responses with the CA key."""

import asyncio
import datetime

import pytest

pytest.importorskip("cryptography")

from emqx_tpu.transport.ocsp import OcspCache, OcspError

from cryptography import x509
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import rsa
from cryptography.hazmat.primitives.serialization import Encoding
from cryptography.x509.oid import (
    AuthorityInformationAccessOID, NameOID,
)


def run(coro):
    return asyncio.run(coro)


def _name(cn):
    return x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, cn)])


def make_pki(aia_url="http://ocsp.test/resp"):
    now = datetime.datetime.now(datetime.timezone.utc)
    ca_key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    ca = (
        x509.CertificateBuilder()
        .subject_name(_name("test-ca")).issuer_name(_name("test-ca"))
        .public_key(ca_key.public_key())
        .serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(days=1))
        .not_valid_after(now + datetime.timedelta(days=365))
        .add_extension(x509.BasicConstraints(ca=True, path_length=None),
                       critical=True)
        .sign(ca_key, hashes.SHA256())
    )
    srv_key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    builder = (
        x509.CertificateBuilder()
        .subject_name(_name("broker.test")).issuer_name(_name("test-ca"))
        .public_key(srv_key.public_key())
        .serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(days=1))
        .not_valid_after(now + datetime.timedelta(days=30))
    )
    if aia_url:
        builder = builder.add_extension(
            x509.AuthorityInformationAccess([
                x509.AccessDescription(
                    AuthorityInformationAccessOID.OCSP,
                    x509.UniformResourceIdentifier(aia_url)),
            ]),
            critical=False,
        )
    srv = builder.sign(ca_key, hashes.SHA256())
    return ca, ca_key, srv, srv_key


def make_responder(ca, ca_key, srv, *, status="good",
                   next_update_s=3600.0, this_update_skew_s=0.0):
    """fetch(url, der_request) building real signed OCSP responses."""
    from cryptography.x509 import ocsp

    calls = []

    async def fetch(url, der_request):
        calls.append(url)
        req = ocsp.load_der_ocsp_request(der_request)
        assert req.serial_number == srv.serial_number
        now = datetime.datetime.now(datetime.timezone.utc)
        cert_status = {
            "good": ocsp.OCSPCertStatus.GOOD,
            "revoked": ocsp.OCSPCertStatus.REVOKED,
        }[status]
        builder = ocsp.OCSPResponseBuilder().add_response(
            cert=srv, issuer=ca, algorithm=hashes.SHA256(),
            cert_status=cert_status,
            this_update=now + datetime.timedelta(seconds=this_update_skew_s),
            next_update=now + datetime.timedelta(seconds=next_update_s),
            revocation_time=(now if status == "revoked" else None),
            revocation_reason=(
                x509.ReasonFlags.key_compromise
                if status == "revoked" else None),
        ).responder_id(ocsp.OCSPResponderEncoding.NAME, ca)
        resp = builder.sign(ca_key, hashes.SHA256())
        return resp.public_bytes(Encoding.DER)

    fetch.calls = calls
    return fetch


def pems(ca, srv):
    return (srv.public_bytes(Encoding.PEM), ca.public_bytes(Encoding.PEM))


def test_refresh_good_and_staple_served():
    ca, ca_key, srv, _k = make_pki()
    cert_pem, issuer_pem = pems(ca, srv)
    fetch = make_responder(ca, ca_key, srv)
    cache = OcspCache(cert_pem, issuer_pem, fetch=fetch)
    # responder URL came from the certificate's AIA extension
    assert cache.responder_url == "http://ocsp.test/resp"
    status = run(cache.refresh())
    assert status == "good"
    assert cache.current() is not None
    info = cache.info()
    assert info["stapled"] and info["status"] == "good"
    assert info["refreshes"] == 1 and fetch.calls == ["http://ocsp.test/resp"]


def test_revoked_status_surfaces():
    ca, ca_key, srv, _k = make_pki()
    cache = OcspCache(*pems(ca, srv),
                      fetch=make_responder(ca, ca_key, srv, status="revoked"))
    assert run(cache.refresh()) == "revoked"
    # the revoked response IS the staple (clients must see the proof)
    assert cache.current() is not None


def test_expired_staple_not_served():
    import time

    ca, ca_key, srv, _k = make_pki()
    cache = OcspCache(*pems(ca, srv),
                      fetch=make_responder(ca, ca_key, srv,
                                           next_update_s=3600))
    run(cache.refresh())
    assert cache.current() is not None
    cache._next_update = time.time() - 1    # the staple just expired
    assert cache.current() is None          # expired: unstapled fail-open


def test_refresh_sleep_tracks_next_update():
    """A short-lived response pulls the next refresh AHEAD of expiry
    (review finding: a 10-minute window must not wait out a 1-hour
    interval unstapled)."""
    import time

    ca, ca_key, srv, _k = make_pki()
    cache = OcspCache(*pems(ca, srv),
                      refresh_interval_s=3600.0,
                      fetch=make_responder(ca, ca_key, srv,
                                           next_update_s=600))
    run(cache.refresh())
    sleep = cache._next_sleep()
    # ~ (600 - margin 60); definitely nowhere near 3600
    assert 400 < sleep < 600
    # and the floor holds for pathologically short windows
    cache._next_update = time.time() + 5
    assert cache._next_sleep() == cache.MIN_SLEEP_S


def test_failures_counted_once():
    ca, ca_key, srv, _k = make_pki()

    async def broken(url, der):
        raise OSError("nope")

    cache = OcspCache(*pems(ca, srv), fetch=broken)
    with pytest.raises(OSError):
        run(cache.refresh())
    assert cache.failures == 1
    cache2 = OcspCache(*pems(ca, srv),
                       fetch=make_responder(ca, ca_key, srv,
                                            this_update_skew_s=900))
    with pytest.raises(OcspError):
        run(cache2.refresh())
    assert cache2.failures == 1


def test_responder_failure_keeps_last_good_response():
    ca, ca_key, srv, _k = make_pki()
    good = make_responder(ca, ca_key, srv)

    async def flaky(url, der):
        if flaky.fail:
            raise OSError("responder unreachable")
        return await good(url, der)

    flaky.fail = False
    cache = OcspCache(*pems(ca, srv), fetch=flaky)
    run(cache.refresh())
    staple = cache.current()
    assert staple is not None
    flaky.fail = True
    with pytest.raises(OSError):
        run(cache.refresh())
    assert cache.current() == staple        # stale-while-refresh


def test_future_dated_response_rejected():
    ca, ca_key, srv, _k = make_pki()
    cache = OcspCache(
        *pems(ca, srv),
        fetch=make_responder(ca, ca_key, srv, this_update_skew_s=900))
    with pytest.raises(OcspError):
        run(cache.refresh())
    assert cache.current() is None


def test_no_aia_and_no_override_is_an_error():
    ca, ca_key, srv, _k = make_pki(aia_url=None)
    cache = OcspCache(*pems(ca, srv),
                      fetch=make_responder(ca, ca_key, srv))
    assert cache.responder_url is None
    with pytest.raises(OcspError):
        run(cache.refresh())


def test_node_wires_ocsp_cache(tmp_path):
    """listeners.ssl.default.ocsp.enable builds the cache from the
    configured cert pair and exposes the health surface."""
    from cryptography.hazmat.primitives.serialization import (
        NoEncryption, PrivateFormat,
    )
    from emqx_tpu.config import Config
    from emqx_tpu.node import BrokerNode

    ca, ca_key, srv, srv_key = make_pki()
    (tmp_path / "srv.pem").write_bytes(srv.public_bytes(Encoding.PEM))
    (tmp_path / "srv.key").write_bytes(srv_key.private_bytes(
        Encoding.PEM, PrivateFormat.TraditionalOpenSSL, NoEncryption()))
    (tmp_path / "ca.pem").write_bytes(ca.public_bytes(Encoding.PEM))

    async def main():
        cfg = Config(file_text=(
            'listeners.tcp.default.bind = "127.0.0.1:0"\n'
            'listeners.ssl.default.enable = true\n'
            'listeners.ssl.default.bind = "127.0.0.1:0"\n'
            f'listeners.ssl.default.certfile = "{tmp_path}/srv.pem"\n'
            f'listeners.ssl.default.keyfile = "{tmp_path}/srv.key"\n'
            f'listeners.ssl.default.cacertfile = "{tmp_path}/ca.pem"\n'
            'listeners.ssl.default.ocsp.enable = true\n'
            'listeners.ssl.default.ocsp.responder_url = '
            '"http://127.0.0.1:1/ocsp"\n'
            'listeners.ssl.default.ocsp.refresh_interval = 3600s\n'
        ))
        node = BrokerNode(cfg)
        await node.start()
        try:
            assert node.ocsp_cache is not None
            info = node.ocsp_cache.info()
            assert info["responder_url"] == "http://127.0.0.1:1/ocsp"
            # swap in the mocked responder and refresh through the cache
            node.ocsp_cache._fetch = make_responder(ca, ca_key, srv)
            assert await node.ocsp_cache.refresh() == "good"
            assert node.ocsp_cache.current() is not None
        finally:
            await node.stop()
            assert node.ocsp_cache is None

    run(main())


def test_wrong_serial_rejected():
    """A response for a DIFFERENT certificate must not install."""
    ca, ca_key, srv, _k = make_pki()
    _ca2, _k2, other, _ok = make_pki()
    cache = OcspCache(*pems(ca, srv),
                      fetch=make_responder(ca, ca_key, other))
    with pytest.raises(Exception):
        run(cache.refresh())
    assert cache.current() is None


def test_forged_signature_rejected():
    """A response signed by someone other than the issuer must not
    install (OCSP rides plain HTTP)."""
    ca, ca_key, srv, _k = make_pki()
    mitm_ca, mitm_key, _s, _mk = make_pki()

    from cryptography.x509 import ocsp as _o

    async def forged(url, der_request):
        now = datetime.datetime.now(datetime.timezone.utc)
        builder = _o.OCSPResponseBuilder().add_response(
            cert=srv, issuer=ca, algorithm=hashes.SHA256(),
            cert_status=_o.OCSPCertStatus.GOOD,
            this_update=now, next_update=now + datetime.timedelta(hours=1),
            revocation_time=None, revocation_reason=None,
        ).responder_id(_o.OCSPResponderEncoding.NAME, mitm_ca)
        return builder.sign(mitm_key, hashes.SHA256()).public_bytes(
            Encoding.DER)

    cache = OcspCache(*pems(ca, srv), fetch=forged)
    with pytest.raises(OcspError):
        run(cache.refresh())
    assert cache.current() is None and cache.failures == 1
