"""Build the native hot-path library.

Generates huffman_table.h from the Python HPACK table (single source of
truth: linkerd_tpu/protocol/h2/hpack.py), then compiles
libl5d_native.so into linkerd_tpu/native/.

Usage: python native/build.py
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
OUT_DIR = os.path.join(REPO, "linkerd_tpu", "native")
SO_PATH = os.path.join(OUT_DIR, "libl5d_native.so")


def gen_table() -> str:
    sys.path.insert(0, REPO)
    from linkerd_tpu.protocol.h2.hpack import HUFFMAN_TABLE

    codes = ", ".join(f"0x{c:x}" for c, _ in HUFFMAN_TABLE)
    bits = ", ".join(str(b) for _, b in HUFFMAN_TABLE)
    return (
        "// GENERATED by native/build.py from hpack.py HUFFMAN_TABLE\n"
        "#pragma once\n#include <cstdint>\n"
        f"static const uint32_t HUFF_CODES[257] = {{{codes}}};\n"
        f"static const uint8_t HUFF_BITS[257] = {{{bits}}};\n"
    )


def gen_stress_cert(tmpdir: str) -> bool:
    """Self-signed cert/key for the stress drivers' TLS leg (openssl
    CLI; skipped — drivers run cleartext-only — when unavailable)."""
    cert = os.path.join(tmpdir, "cert.pem")
    key = os.path.join(tmpdir, "key.pem")
    try:
        subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048",
             "-keyout", key, "-out", cert, "-days", "2", "-nodes",
             "-subj", "/CN=localhost"],
            check=True, capture_output=True, timeout=60)
    except (OSError, subprocess.SubprocessError) as e:
        print(f"stress cert generation failed ({e}); TLS leg skipped")
        return False
    return True


def build_sanitized(sanitizer: str) -> int:
    """Build + run the sanitizer stress drivers for BOTH native engines
    (SURVEY.md §5: the C++ pieces must be TSan-cleanable), each with a
    TLS leg when a cert can be generated and the OpenSSL runtime loads.
    Exit 0 = traffic flowed through each AND the sanitizer reported
    nothing (halt_on_error aborts otherwise)."""
    import tempfile
    env = dict(os.environ)
    opt = "TSAN_OPTIONS" if sanitizer == "thread" else "ASAN_OPTIONS"
    env[opt] = "halt_on_error=1 exitcode=66"
    targets = [
        ("h1", [os.path.join(HERE, "fastpath.cpp"),
                os.path.join(HERE, "tsan_stress.cpp")]),
        ("h2", [os.path.join(HERE, "h2_fastpath.cpp"),
                os.path.join(HERE, "tsan_h2_stress.cpp")]),
    ]
    with tempfile.TemporaryDirectory(prefix="l5d-stress-") as tmpdir:
        if gen_stress_cert(tmpdir):
            env["L5D_STRESS_CERT"] = os.path.join(tmpdir, "cert.pem")
            env["L5D_STRESS_KEY"] = os.path.join(tmpdir, "key.pem")
        for name, srcs in targets:
            bin_path = os.path.join(HERE, f"{sanitizer}_{name}_stress")
            cmd = ["g++", "-O1", "-g", "-std=c++17", "-Wall",
                   f"-fsanitize={sanitizer}", *srcs, "-o", bin_path,
                   "-lpthread", "-ldl"]
            print(" ".join(cmd))
            subprocess.check_call(cmd)
            print(f"running {bin_path} ({opt}={env[opt]})")
            rc = subprocess.call([bin_path], env=env)
            if rc == 0:
                print(f"{sanitizer} sanitizer [{name}]: CLEAN")
            else:
                print(f"{sanitizer} sanitizer [{name}]: FAILED (rc={rc})")
                return rc
    return 0


def build_h2bench() -> str:
    """Build (if stale) and return the out-of-process h2 bench binary
    used by benchmarks/config2_grpc.py as its external load generator
    and echo backend."""
    src = os.path.join(HERE, "h2bench.cpp")
    out = os.path.join(HERE, "h2bench")
    hdr = os.path.join(HERE, "h2_core.h")
    huff = os.path.join(HERE, "huffman_table.h")
    if not os.path.exists(huff):
        with open(huff, "w") as f:
            f.write(gen_table())
    deps = [src, hdr, huff, os.path.join(HERE, "tls_shim.h")]
    if (not os.path.exists(out)
            or os.path.getmtime(out) < max(os.path.getmtime(d)
                                           for d in deps)):
        subprocess.check_call(["g++", "-O2", "-std=c++17", "-Wall", src,
                               "-o", out, "-lpthread", "-ldl"])
    return out


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--sanitize", nargs="?", const="thread",
                    choices=["thread", "address"],
                    help="build fastpath with -fsanitize and run the "
                         "stress driver instead of building the .so")
    args = ap.parse_args()

    os.makedirs(OUT_DIR, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=HERE, prefix=".huffman_table.")
    with os.fdopen(fd, "w") as f:
        f.write(gen_table())
    os.chmod(tmp, 0o644)
    os.replace(tmp, os.path.join(HERE, "huffman_table.h"))
    if args.sanitize:
        return build_sanitized(args.sanitize)
    # pytest's workers may build at once: each links into a file of its
    # own and renames it into place, so none loads a half-written library
    fd, tmp = tempfile.mkstemp(dir=OUT_DIR, prefix=".libl5d_native.",
                               suffix=".so")
    os.close(fd)
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-Wall",
        os.path.join(HERE, "l5d_native.cpp"),
        os.path.join(HERE, "fastpath.cpp"),
        os.path.join(HERE, "h2_fastpath.cpp"),
        "-o", tmp, "-lpthread", "-ldl",
    ]
    print(" ".join(cmd))
    try:
        subprocess.check_call(cmd)
        os.chmod(tmp, 0o755)
        os.replace(tmp, SO_PATH)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    print(f"built {SO_PATH}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
