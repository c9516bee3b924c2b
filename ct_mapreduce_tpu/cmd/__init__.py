"""CLI entry points, mirroring the reference's three binaries
(/root/reference/cmd/): ct-fetch, storage-statistics, ct-getcert."""

from __future__ import annotations

import sys

_ISSUER_FLAGS = ("-issuer", "--issuer", "-issuerMeta", "--issuerMeta")


def glue_issuer_ids(argv: list[str] | None) -> list[str]:
    """An issuerID is base64url, so one in 64 begins with ``-``, and
    argparse reads a value of that look as an option: hand it
    ``<flag>=<id>`` wherever the ID follows its flag."""
    args = iter(sys.argv[1:] if argv is None else argv)
    out: list[str] = []
    for arg in args:
        value = next(args, None) if arg in _ISSUER_FLAGS else None
        out.append(arg if value is None else f"{arg}={value}")
    return out
