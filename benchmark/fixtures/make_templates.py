#!/usr/bin/env python3
"""Regenerate ``templates.json``: one CA per issuer and, under each CA,
one signed EC-P256 leaf and one signed RSA-2048 leaf whose 16-byte
serial the fixture restamps. Run by hand (needs ``cryptography``); the
benchmark itself only reads the JSON, so every run of every seed serves
byte-identical certificates apart from the serials.

The leaf shape is a copy of ``ct_mapreduce_tpu/utils/syncerts.py``'s
``rich_extensions`` template (SAN, AIA, KU, EKU, SKI, AKI, policies and
a two-SCT stand-in): RSA leaves land near 1.5 KB of DER, EC leaves near
1.0 KB, so every 65,536-entry batch packs at the 2048-byte pad bucket.
"""

from __future__ import annotations

import base64
import datetime
import hashlib
import json
import os
import sys

from cryptography import x509
from cryptography.hazmat.primitives import hashes, serialization
from cryptography.hazmat.primitives.asymmetric import ec, rsa
from cryptography.x509.oid import NameOID

ISSUERS = 16
SERIAL_LEN = 16
NOT_BEFORE = datetime.datetime(2024, 1, 1, tzinfo=datetime.timezone.utc)
NOT_AFTER = datetime.datetime(2031, 6, 15, 14, tzinfo=datetime.timezone.utc)
TEMPLATE_SERIAL = int.from_bytes(b"\x4d" + b"\x00" * (SERIAL_LEN - 1), "big")


def leaf(issuer_name, ca_key, leaf_key) -> bytes:
    pub = leaf_key.public_key()
    builder = (
        x509.CertificateBuilder()
        .subject_name(x509.Name(
            [x509.NameAttribute(NameOID.COMMON_NAME, "bench.example.com")]))
        .issuer_name(issuer_name)
        .public_key(pub)
        .serial_number(TEMPLATE_SERIAL)
        .not_valid_before(NOT_BEFORE)
        .not_valid_after(NOT_AFTER)
        .add_extension(x509.BasicConstraints(ca=False, path_length=None),
                       critical=True)
        .add_extension(x509.CRLDistributionPoints([x509.DistributionPoint(
            full_name=[x509.UniformResourceIdentifier(
                "http://crl.bench.example/latest.crl")],
            relative_name=None, reasons=None, crl_issuer=None)]),
            critical=False)
        .add_extension(x509.SubjectAlternativeName([
            x509.DNSName("bench.example.com"),
            x509.DNSName("www.bench.example.com"),
            x509.DNSName("cdn.bench.example.com")]), critical=False)
        .add_extension(x509.AuthorityInformationAccess([
            x509.AccessDescription(
                x509.oid.AuthorityInformationAccessOID.OCSP,
                x509.UniformResourceIdentifier("http://ocsp.bench.example")),
            x509.AccessDescription(
                x509.oid.AuthorityInformationAccessOID.CA_ISSUERS,
                x509.UniformResourceIdentifier(
                    "http://ca.bench.example/issuer.crt"))]), critical=False)
        .add_extension(x509.KeyUsage(
            digital_signature=True, key_encipherment=True,
            content_commitment=False, data_encipherment=False,
            key_agreement=False, key_cert_sign=False, crl_sign=False,
            encipher_only=False, decipher_only=False), critical=True)
        .add_extension(x509.ExtendedKeyUsage([
            x509.oid.ExtendedKeyUsageOID.SERVER_AUTH,
            x509.oid.ExtendedKeyUsageOID.CLIENT_AUTH]), critical=False)
        .add_extension(x509.SubjectKeyIdentifier.from_public_key(pub),
                       critical=False)
        .add_extension(x509.AuthorityKeyIdentifier.from_issuer_public_key(
            ca_key.public_key()), critical=False)
        .add_extension(x509.CertificatePolicies([x509.PolicyInformation(
            x509.ObjectIdentifier("2.23.140.1.2.1"), None)]), critical=False)
        .add_extension(x509.UnrecognizedExtension(
            x509.ObjectIdentifier("1.3.6.1.4.1.11129.2.4.2"),
            bytes([0x04, 0xF6, 0x00, 0xF4]) + bytes(244)), critical=False)
    )
    return builder.sign(ca_key, hashes.SHA256()).public_bytes(
        serialization.Encoding.DER)


def main() -> int:
    issuers = []
    for k in range(ISSUERS):
        # CAs alternate RSA and EC keys, as the WebPKI's do.
        ca_key = (rsa.generate_private_key(65537, 2048) if k % 2 == 0
                  else ec.generate_private_key(ec.SECP256R1()))
        name = x509.Name([
            x509.NameAttribute(NameOID.COUNTRY_NAME, "US"),
            x509.NameAttribute(NameOID.ORGANIZATION_NAME, "Bench Org"),
            x509.NameAttribute(NameOID.COMMON_NAME,
                               f"Bench Issuer CA {k:02d}")])
        ca_der = (
            x509.CertificateBuilder()
            .subject_name(name).issuer_name(name)
            .public_key(ca_key.public_key()).serial_number(1)
            .not_valid_before(NOT_BEFORE).not_valid_after(NOT_AFTER)
            .add_extension(x509.BasicConstraints(ca=True, path_length=None),
                           critical=True)
            .sign(ca_key, hashes.SHA256())
            .public_bytes(serialization.Encoding.DER))
        spki = ca_key.public_key().public_bytes(
            serialization.Encoding.DER,
            serialization.PublicFormat.SubjectPublicKeyInfo)
        leaves = {}
        for kind, key in (("ec_p256", ec.generate_private_key(ec.SECP256R1())),
                          ("rsa2048", rsa.generate_private_key(65537, 2048))):
            der = leaf(name, ca_key, key)
            off = der.index(TEMPLATE_SERIAL.to_bytes(SERIAL_LEN, "big"))
            leaves[kind] = {"der": base64.b64encode(der).decode(),
                            "serial_off": off, "der_len": len(der)}
        issuers.append({
            "cn": f"Bench Issuer CA {k:02d}",
            "issuer_der": base64.b64encode(ca_der).decode(),
            "issuer_id": base64.urlsafe_b64encode(
                hashlib.sha256(spki).digest()).decode(),
            "leaves": leaves})
    doc = {"serial_len": SERIAL_LEN,
           "not_after": NOT_AFTER.strftime("%Y-%m-%dT%H:%M:%SZ"),
           "exp_date_id": NOT_AFTER.strftime("%Y-%m-%d-%H"),
           "issuers": issuers}
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "templates.json")
    with open(out, "w") as fh:
        json.dump(doc, fh, indent=1)
        fh.write("\n")
    print(out, os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
