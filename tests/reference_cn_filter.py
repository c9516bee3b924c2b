"""The plain reference of ``issuerCNFilter``: ``certIsFilteredOut``
(/root/reference/cmd/ct-fetch/ct-fetch.go:44-70) entry by entry, a dict
of sets for what passes and three integers for what is dropped. No
batching, no device, nothing of the program: ``cryptography``'s parse,
``hashlib`` and ``base64`` (and a few lines that read BasicConstraints
alone where ``cryptography`` refuses a certificate's other extensions).

    ref = Reference("Let's Encrypt,Bench Issuer CA 00", now)
    ref.feed(leaf_der, ca_der)      # one entry and the CA that signs it
    ref.serials                     # {issuer id: {raw serial bytes}}
    ref.dropped                     # {"CA": n, "expired": n, "cn": n}

The same semantics as ct-fetch's, in its order: a CA certificate is
dropped (BasicConstraints present and ``cA`` true); then one whose
``notAfter`` lies before ``now``, by the instant; then, where the
directive holds anything, one whose issuer's CommonName starts with
none of its comma-separated prefixes. The CommonName is the one Go's
``pkix.Name.FillFromRDNSequence`` leaves in ``cert.Issuer.CommonName``:
the LAST attribute of type 2.5.4.3 in the whole Name, the attributes of
a multi-valued RDN in their encoded order, among the value types
``encoding/asn1`` returns as a string; ``""`` where there is none. An
empty element of the directive (``"a,,b"``, a trailing comma) is a
prefix of every name, as ``strings.HasPrefix(name, "")`` is true.
"""

from __future__ import annotations

import base64
import datetime
import hashlib

from cryptography import x509
from cryptography.hazmat.primitives import serialization
from cryptography.x509.name import _ASN1Type
from cryptography.x509.oid import NameOID

# What Go's encoding/asn1 hands pkix.Name as a string.
GO_STRING_TYPES = (_ASN1Type.UTF8String, _ASN1Type.PrintableString,
                   _ASN1Type.T61String, _ASN1Type.IA5String,
                   _ASN1Type.NumericString, _ASN1Type.BMPString)


def go_common_name(name: x509.Name) -> str:
    cn = ""
    for rdn in name.rdns:
        for attr in rdn:
            if attr.oid == NameOID.COMMON_NAME and attr._type in GO_STRING_TYPES:
                cn = attr.value
    return cn


def issuer_id(ca_der: bytes) -> str:
    """base64url(SHA-256(SubjectPublicKeyInfo)) of the CA, padding kept
    (/root/reference/storage/types.go:104-141)."""
    spki = x509.load_der_x509_certificate(ca_der).public_key().public_bytes(
        serialization.Encoding.DER,
        serialization.PublicFormat.SubjectPublicKeyInfo)
    return base64.urlsafe_b64encode(hashlib.sha256(spki).digest()).decode()


def raw_serial(der: bytes) -> bytes:
    """The serialNumber's content bytes as encoded, sign byte and all
    (/root/reference/storage/types.go:165-178). ``cryptography`` gives
    the integer, so the bytes are DER's minimal encoding of it."""
    n = x509.load_der_x509_certificate(der).serial_number
    return n.to_bytes(n.bit_length() // 8 + 1, "big")


def _tlv(buf: bytes, off: int) -> tuple[int, int, int]:
    """``(tag, content offset, content end)`` of the DER element at
    ``off``."""
    n = buf[off + 1]
    if n < 0x80:
        return buf[off], off + 2, off + 2 + n
    k = n & 0x7F
    length = int.from_bytes(buf[off + 2:off + 2 + k], "big")
    return buf[off], off + 2 + k, off + 2 + k + length


def basic_constraints_ca(tbs: bytes) -> bool:
    """``cA`` of the BasicConstraints extension (2.5.29.19), False
    where there is none: the TBSCertificate's last element, ``[3]``, read
    element by element. Only for a certificate whose extensions
    ``cryptography`` refuses to decode as a whole (the committed
    templates carry filler in their SCT list); the tests hold it to
    ``cryptography``'s answer wherever that parses."""
    _tag, pos, end = _tlv(tbs, 0)
    while pos < end:
        tag, lo, hi = _tlv(tbs, pos)
        if tag == 0xA3:
            _seq, pos, end = _tlv(tbs, lo)
            while pos < end:
                _ext, lo, hi = _tlv(tbs, pos)
                _oid, o_lo, o_hi = _tlv(tbs, lo)
                if tbs[o_lo:o_hi] == b"\x55\x1d\x13":
                    at = o_hi
                    while at < hi:  # critical BOOLEAN, then the OCTET STRING
                        tag, v_lo, v_hi = _tlv(tbs, at)
                        at = v_hi
                    _seq, b_lo, b_hi = _tlv(tbs, v_lo)
                    return (b_lo < b_hi and tbs[b_lo] == 0x01
                            and tbs[b_lo + 2] != 0)
                pos = hi
            return False
        pos = hi
    return False


def is_ca(cert: x509.Certificate) -> bool:
    try:
        bc = cert.extensions.get_extension_for_class(
            x509.BasicConstraints).value
    except x509.ExtensionNotFound:
        return False
    except ValueError:
        return basic_constraints_ca(cert.tbs_certificate_bytes)
    return bool(bc.ca)


class Reference:
    def __init__(self, directive: str, now: datetime.datetime):
        self.prefixes = directive.split(",") if directive else []
        self.now = now
        self.serials: dict[str, set[bytes]] = {}
        self.dropped = {"CA": 0, "expired": 0, "cn": 0}
        self._ids: dict[bytes, str] = {}

    def filtered_out(self, cert: x509.Certificate) -> str | None:
        if is_ca(cert):
            return "CA"
        if cert.not_valid_after_utc < self.now:
            return "expired"
        if self.prefixes:
            cn = go_common_name(cert.issuer)
            if not any(cn.startswith(p) for p in self.prefixes):
                return "cn"
        return None

    def feed(self, leaf_der: bytes, ca_der: bytes) -> str | None:
        """One entry; returns why it was dropped, or None."""
        why = self.filtered_out(x509.load_der_x509_certificate(leaf_der))
        if why is not None:
            self.dropped[why] += 1
            return why
        if ca_der not in self._ids:
            self._ids[ca_der] = issuer_id(ca_der)
        self.serials.setdefault(self._ids[ca_der], set()).add(
            raw_serial(leaf_der))
        return None

    def counts(self) -> dict[str, int]:
        return {i: len(s) for i, s in self.serials.items()}
