"""Synthetic certificate streams for benchmarks and dry runs.

The reference generates fixtures on the fly with Go's stdlib x509
(``makeCert``, /root/reference/storage/issuermetadata_test.go:62-98).
Signing a fresh key pair per certificate is far too slow for
millions-of-entries benchmark replays, so this module builds ONE real
signed template per issuer (via ``cryptography``) and then stamps out
arbitrarily many structurally-valid variants by patching the serial
INTEGER bytes in place — the parse/filter/fingerprint/dedup pipeline
never verifies signatures, exactly like the reference's ingest path
(/root/reference/cmd/ct-fetch/ct-fetch.go:198-226 parses, never
verifies chains).

Serials are fixed-length with a constant positive first byte, so DER
lengths never change and every variant remains canonical DER.
"""

from __future__ import annotations

import datetime
from dataclasses import dataclass

import numpy as np

from ct_mapreduce_tpu.core import der as hostder

SERIAL_LEN = 16  # bytes of DER INTEGER content in the template


@dataclass
class CertTemplate:
    """A signed leaf template whose serial window can be restamped."""

    leaf_der: bytes
    issuer_der: bytes
    serial_off: int  # offset of the serial content bytes in leaf_der
    serial_len: int


def _build_pair(
    issuer_cn: str,
    not_after: datetime.datetime,
    crl_dp: str | None,
    key_type: str = "ec",
    serial_len: int = SERIAL_LEN,
    rich_extensions: bool = False,
) -> tuple[bytes, bytes]:
    try:
        from cryptography import x509
        from cryptography.hazmat.primitives import hashes, serialization
        from cryptography.hazmat.primitives.asymmetric import ec, rsa
        from cryptography.x509.oid import NameOID
    except ImportError:
        # Hosts without the cryptography package (some CI containers)
        # fall back to the hand-assembled canonical-DER builder: same
        # parse/filter/fingerprint behavior, synthetic signature bytes
        # (nothing on the ingest path verifies). Row-size realism is
        # approximated with opaque extension padding.
        return _build_pair_minicert(
            issuer_cn, not_after, crl_dp, key_type=key_type,
            serial_len=serial_len, rich_extensions=rich_extensions)

    # Real CT logs are RSA-dominated (~1.2-1.9 KB DER vs ~0.8 KB for
    # ECDSA P-256): RSA templates exist so benchmarks can measure the
    # realistic row-bytes regime, not just the friendly one.
    if key_type == "rsa2048":
        key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    elif key_type == "ec":
        key = ec.generate_private_key(ec.SECP256R1())
    else:
        raise ValueError(f"unknown key_type {key_type!r} (ec | rsa2048)")
    issuer_name = x509.Name(
        [
            x509.NameAttribute(NameOID.COUNTRY_NAME, "US"),
            x509.NameAttribute(NameOID.ORGANIZATION_NAME, "Bench Org"),
            x509.NameAttribute(NameOID.COMMON_NAME, issuer_cn),
        ]
    )
    now = datetime.datetime(2024, 1, 1, tzinfo=datetime.timezone.utc)

    issuer_builder = (
        x509.CertificateBuilder()
        .subject_name(issuer_name)
        .issuer_name(issuer_name)
        .public_key(key.public_key())
        .serial_number(1)
        .not_valid_before(now)
        .not_valid_after(not_after)
        .add_extension(x509.BasicConstraints(ca=True, path_length=None), critical=True)
    )
    issuer_der = issuer_builder.sign(key, hashes.SHA256()).public_bytes(
        serialization.Encoding.DER
    )

    # Template serial: serial_len bytes, first byte 0x4D (positive, no
    # leading-zero trimming) so every restamp keeps identical DER shape.
    serial_int = int.from_bytes(b"\x4d" + b"\x00" * (serial_len - 1), "big")
    leaf_builder = (
        x509.CertificateBuilder()
        .subject_name(
            x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, "bench.example.com")])
        )
        .issuer_name(issuer_name)
        .public_key(key.public_key())
        .serial_number(serial_int)
        .not_valid_before(now)
        .not_valid_after(not_after)
        .add_extension(x509.BasicConstraints(ca=False, path_length=None), critical=True)
    )
    if crl_dp:
        leaf_builder = leaf_builder.add_extension(
            x509.CRLDistributionPoints(
                [
                    x509.DistributionPoint(
                        full_name=[x509.UniformResourceIdentifier(crl_dp)],
                        relative_name=None,
                        reasons=None,
                        crl_issuer=None,
                    )
                ]
            ),
            critical=False,
        )
    if rich_extensions:
        # The production extension load (SAN, AIA, KU, EKU, SKI, AKI)
        # that puts real leaf certs in the 1.2-1.9 KB regime — the
        # walker's extension scan must be benchmarked against this
        # shape, not just the minimal template.
        leaf_builder = (
            leaf_builder
            .add_extension(
                x509.SubjectAlternativeName([
                    x509.DNSName("bench.example.com"),
                    x509.DNSName("www.bench.example.com"),
                    x509.DNSName("cdn.bench.example.com"),
                ]),
                critical=False,
            )
            .add_extension(
                x509.AuthorityInformationAccess([
                    x509.AccessDescription(
                        x509.oid.AuthorityInformationAccessOID.OCSP,
                        x509.UniformResourceIdentifier(
                            "http://ocsp.bench.example"),
                    ),
                    x509.AccessDescription(
                        x509.oid.AuthorityInformationAccessOID.CA_ISSUERS,
                        x509.UniformResourceIdentifier(
                            "http://ca.bench.example/issuer.crt"),
                    ),
                ]),
                critical=False,
            )
            .add_extension(
                x509.KeyUsage(
                    digital_signature=True, key_encipherment=True,
                    content_commitment=False, data_encipherment=False,
                    key_agreement=False, key_cert_sign=False,
                    crl_sign=False, encipher_only=False,
                    decipher_only=False,
                ),
                critical=True,
            )
            .add_extension(
                x509.ExtendedKeyUsage([
                    x509.oid.ExtendedKeyUsageOID.SERVER_AUTH,
                    x509.oid.ExtendedKeyUsageOID.CLIENT_AUTH,
                ]),
                critical=False,
            )
            .add_extension(
                x509.SubjectKeyIdentifier.from_public_key(key.public_key()),
                critical=False,
            )
            .add_extension(
                x509.AuthorityKeyIdentifier.from_issuer_public_key(
                    key.public_key()),
                critical=False,
            )
            .add_extension(
                x509.CertificatePolicies([
                    x509.PolicyInformation(
                        x509.ObjectIdentifier("2.23.140.1.2.1"), None),
                ]),
                critical=False,
            )
            # Embedded SCT list stand-in (OID 1.3.6.1.4.1.11129.2.4.2):
            # CT leaves carry ~120 B per SCT; two logs' worth of opaque
            # bytes reproduces the real extension-scan workload.
            .add_extension(
                x509.UnrecognizedExtension(
                    x509.ObjectIdentifier("1.3.6.1.4.1.11129.2.4.2"),
                    bytes([0x04, 0xF6, 0x00, 0xF4]) + bytes(244),
                ),
                critical=False,
            )
        )
    leaf_der = leaf_builder.sign(key, hashes.SHA256()).public_bytes(
        serialization.Encoding.DER
    )
    return leaf_der, issuer_der


def _build_pair_minicert(
    issuer_cn: str,
    not_after: datetime.datetime,
    crl_dp: str | None,
    key_type: str = "ec",
    serial_len: int = SERIAL_LEN,
    rich_extensions: bool = False,
) -> tuple[bytes, bytes]:
    from ct_mapreduce_tpu.utils import minicert

    if key_type not in ("ec", "rsa2048"):
        raise ValueError(f"unknown key_type {key_type!r} (ec | rsa2048)")
    # Size realism without a signer: RSA-2048 leaves carry ~550 B more
    # key+signature DER than P-256; the production extension load adds
    # ~700 B (SAN/AIA/KU/EKU/SKI/AKI/policies/SCTs) — pad with one
    # opaque extension so row-byte-proportional code paths (narrow
    # pre-decode, H2D volume) see the same regime.
    extra = 0
    if key_type == "rsa2048":
        extra += 550
    if rich_extensions:
        extra += 700
    issuer_der = minicert.make_cert(
        serial=1, issuer_cn=issuer_cn, is_ca=True, not_after=not_after)
    leaf_der = minicert.make_cert(
        serial=0, issuer_cn=issuer_cn, subject_cn="bench.example.com",
        is_ca=False, not_after=not_after,
        crl_dps=(crl_dp,) if crl_dp else (),
        serial_len=serial_len, extra_ext_bytes=extra)
    return leaf_der, issuer_der


def make_template(
    issuer_cn: str = "Bench Issuer CA",
    not_after: datetime.datetime | None = None,
    crl_dp: str | None = "http://crl.bench.example/latest.crl",
    key_type: str = "ec",
    serial_len: int = SERIAL_LEN,
    rich_extensions: bool = False,
) -> CertTemplate:
    if not 8 <= serial_len <= 20:
        # < 8 leaves no room for the epoch+lane counter fields the
        # device stampers use; > 20 exceeds RFC 5280's serial bound.
        raise ValueError(f"serial_len {serial_len} outside 8..20")
    not_after = not_after or datetime.datetime(
        2031, 6, 15, tzinfo=datetime.timezone.utc
    )
    leaf_der, issuer_der = _build_pair(
        issuer_cn, not_after, crl_dp, key_type=key_type,
        serial_len=serial_len, rich_extensions=rich_extensions)
    fields = hostder.parse_cert(leaf_der)
    assert fields.serial_len == serial_len, fields.serial_len
    return CertTemplate(
        leaf_der=leaf_der,
        issuer_der=issuer_der,
        serial_off=fields.serial_off,
        serial_len=fields.serial_len,
    )


def stamp_serial(template: CertTemplate, counter: int) -> bytes:
    """One DER variant: template with serial content = 0x4D ‖ counter."""
    n = template.serial_len
    body = counter.to_bytes(n - 1, "big")
    der = bytearray(template.leaf_der)
    der[template.serial_off + 1 : template.serial_off + n] = body
    return bytes(der)


def stamp_batch_array(
    template: CertTemplate,
    start: int,
    batch: int,
    pad_len: int,
    rng_mix: int = 0,
) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized restamp: uint8[batch, pad_len] data + int32 lengths.

    Serials are ``start..start+batch`` mixed with ``rng_mix`` so
    successive epochs produce disjoint serial spaces. This is the fast
    path for benchmark replay — no per-entry Python loop.
    """
    base = np.frombuffer(template.leaf_der, dtype=np.uint8)
    if base.size > pad_len:
        raise ValueError(f"template ({base.size}B) exceeds pad length {pad_len}")
    data = np.zeros((batch, pad_len), dtype=np.uint8)
    data[:, : base.size] = base[None, :]
    counters = (np.arange(start, start + batch, dtype=np.uint64)
                ^ np.uint64(rng_mix))
    # big-endian expansion of the counter into the low serial bytes
    # (8 of them, or serial_len - 1 for short serials — byte 0 stays
    # the fixed positive 0x4D either way)
    off = template.serial_off
    for i in range(min(8, template.serial_len - 1)):
        data[:, off + template.serial_len - 1 - i] = (
            (counters >> np.uint64(8 * i)) & np.uint64(0xFF)
        ).astype(np.uint8)
    lengths = np.full((batch,), base.size, dtype=np.int32)
    return data, lengths


def build_device_batches(
    template: CertTemplate,
    n_batches: int,
    batch: int,
    pad_len: int,
):
    """Synthesize resident batches ON DEVICE from the signed template.

    Returns ``(datas uint8[G, B, pad_len], lens int32[G, B])`` device
    arrays. A per-(batch, lane) uint32 counter (``g * batch + lane``,
    big-endian) is stamped into serial content bytes 12..16 — unique up
    to 2^32 lanes; bytes 4..8 are left zero for callers that restamp a
    per-sweep epoch on device. H2D traffic is
    one ~1 KB template row instead of gigabytes of host-stamped rows.
    """
    import jax
    import jax.numpy as jnp

    base = np.frombuffer(template.leaf_der, dtype=np.uint8)
    if base.size > pad_len:
        raise ValueError(f"template ({base.size}B) exceeds pad length {pad_len}")
    tlen = int(base.size)
    n = template.serial_len
    if n < 12:
        raise ValueError(
            f"serial_len {n} < 12: the lane counter (last 4 bytes) would "
            "collide with the epoch window (bytes 4..8); use the mixed "
            "builder for short serials")
    lane_cols = template.serial_off + np.arange(n - 4, n, dtype=np.int32)

    @jax.jit
    def build(base_row):
        row = jnp.zeros((pad_len,), jnp.uint8).at[:tlen].set(base_row)
        data = jnp.broadcast_to(row, (n_batches, batch, pad_len))
        cnt = (jnp.arange(n_batches, dtype=jnp.uint32)[:, None] * batch
               + jnp.arange(batch, dtype=jnp.uint32)[None, :])
        cb = jnp.stack(
            [(cnt >> 24) & 0xFF, (cnt >> 16) & 0xFF,
             (cnt >> 8) & 0xFF, cnt & 0xFF], axis=-1
        ).astype(jnp.uint8)
        return data.at[:, :, lane_cols].set(cb)

    datas = build(jax.device_put(base))
    lens = jnp.full((n_batches, batch), tlen, dtype=jnp.int32)
    return datas, lens


def make_wire_batch(
    templates: list[CertTemplate],
    start: int,
    n: int,
    ts_base: int = 1_700_000_000_000,
    serials=None,
) -> tuple[list[str], list[str]]:
    """One get-entries response worth of RFC 6962 wire entries
    (base64 leaf_input / extra_data), entries alternating over
    ``templates`` with serials ``start..start+n``. Shared by the e2e
    benchmark leg and the decode-scaling probe so the two measure the
    SAME stream format.

    ``serials`` (length ``n``) replaces the default counters and also
    picks each entry's template (``serial % len(templates)``), so an
    entry that repeats an earlier serial is a true duplicate: same
    issuer, same dedup key.
    """
    import base64

    from ct_mapreduce_tpu.ingest import leaf as leaflib

    eds_cache = [
        base64.b64encode(
            leaflib.encode_extra_data([t.issuer_der])).decode()
        for t in templates
    ]
    lis, eds = [], []
    for j in range(n):
        if serials is None:
            k, serial = j % len(templates), start + j
        else:
            serial = int(serials[j])
            k = serial % len(templates)
        der = stamp_serial(templates[k], serial)
        lis.append(base64.b64encode(
            leaflib.encode_leaf_input(der, ts_base + j)).decode())
        eds.append(eds_cache[k])
    return lis, eds


def zipf_weights(n: int, s: float = 1.1) -> np.ndarray:
    """Zipf issuer split (CT reality: a handful of CAs dominate)."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()
