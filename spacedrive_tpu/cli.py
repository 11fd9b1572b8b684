"""Command-line host.

Covers the reference's `apps/cli` (header inspection,
/root/reference/apps/cli/src/main.rs:14-23 + print_crypto_details) and
adds the obvious node entry points the reference leaves to its server/
desktop hosts: `serve` (HTTP/websocket API host) and one-shot
`encrypt`/`decrypt` for files outside any library.

Usage:
    python -m spacedrive_tpu header  sealed.sdtpu
    python -m spacedrive_tpu serve   --data-dir ~/.spacedrive-tpu
    python -m spacedrive_tpu encrypt plain.bin   [-o out.sdtpu]
    python -m spacedrive_tpu decrypt out.sdtpu   [-o plain.bin]
"""

from __future__ import annotations

import argparse
import getpass
import sys


def _cmd_header(args) -> int:
    from .crypto.header import FileHeader

    try:
        with open(args.path, "rb") as f:
            header = FileHeader.deserialize(f)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    print(f"Header version: {header.version}")
    print(f"Encryption algorithm: {header.algorithm.value}")
    print(f"AAD (hex): {header.aad().hex()}")
    for i, slot in enumerate(header.keyslots):
        print(f"Keyslot {i}:")
        print(f"  Version: {slot.version}")
        print(f"  Hashing algorithm: {slot.hashing_algorithm.value}"
              f" ({slot.hashing_params.value})")
        print(f"  Salt (hex): {slot.salt.hex()}")
        print(f"  Master key (hex, encrypted): "
              f"{slot.encrypted_master_key.hex()}")
        print(f"  Master key nonce (hex): {slot.master_key_nonce.hex()}")
    print(f"Metadata: {'present' if header.metadata else 'none'}")
    print("Preview media: "
          f"{'present' if header.preview_media else 'none'}")
    return 0


def _password(args) -> "object":
    from .crypto.primitives import Protected

    pw = args.password or getpass.getpass("password: ")
    return Protected(pw.encode())


def _cmd_encrypt(args) -> int:
    from .crypto.header import encrypt_file

    import os

    out = args.output or args.path + ".sdtpu"
    if os.path.exists(out):
        print(f"error: output {out} already exists", file=sys.stderr)
        return 1
    password = _password(args)  # prompt before the output file exists
    try:
        # Streaming user output to a caller-chosen path (pre-checked
        # absent above; partial output removed in the except below) —
        # not durable node state.
        # sdlint: ok[io-durability]
        with open(args.path, "rb") as fin, open(out, "wb") as fout:
            encrypt_file(fin, fout, password, metadata={"name": args.path})
    except (OSError, ValueError) as e:
        try:
            os.remove(out)
        except OSError:
            pass
        print(f"error: {e}", file=sys.stderr)
        return 1
    print(out)
    return 0


def _cmd_decrypt(args) -> int:
    import os

    from .crypto.header import decrypt_file

    out = args.output or (
        args.path[:-6] if args.path.endswith(".sdtpu")
        else args.path + ".decrypted")
    if os.path.exists(out):
        print(f"error: output {out} already exists", file=sys.stderr)
        return 1
    password = _password(args)
    try:
        # Same streaming-user-output shape as _cmd_encrypt: the
        # caller owns the target.
        # sdlint: ok[io-durability]
        with open(args.path, "rb") as fin, open(out, "wb") as fout:
            decrypt_file(fin, fout, password)
    except (OSError, ValueError) as e:
        try:
            os.remove(out)
        except OSError:
            pass
        print(f"error: {e}", file=sys.stderr)
        return 1
    print(out)
    return 0


def _cmd_serve(args) -> int:
    import asyncio

    from .api.server import serve
    from .xla_env import enable_compile_cache

    enable_compile_cache()

    try:
        asyncio.run(serve(args.data_dir, args.host, args.port))
    except KeyboardInterrupt:
        pass
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="spacedrive_tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("header", help="inspect an encrypted file's header")
    p.add_argument("path")
    p.set_defaults(fn=_cmd_header)

    p = sub.add_parser("encrypt", help="encrypt a file")
    p.add_argument("path")
    p.add_argument("-o", "--output")
    p.add_argument("-p", "--password")
    p.set_defaults(fn=_cmd_encrypt)

    p = sub.add_parser("decrypt", help="decrypt a file")
    p.add_argument("path")
    p.add_argument("-o", "--output")
    p.add_argument("-p", "--password")
    p.set_defaults(fn=_cmd_decrypt)

    p = sub.add_parser("serve", help="run the node + API server")
    p.add_argument("--data-dir", required=True)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8080)
    p.set_defaults(fn=_cmd_serve)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
