"""Distribution assembly builder — the tez-dist analog.

The reference ships two assemblies (tez-dist/src/main/assembly/tez-dist.xml
and tez-dist-minimal.xml): the full tarball bundles every runtime module
plus dependencies; the minimal one ships only the framework and expects the
environment (Hadoop there, the Python/JAX toolchain here) to be provided.

`tez-dist [--minimal] [--out DIR]` produces
`<out>/tez-tpu-<version>[-minimal].tar.gz`:

- full: the `tez_tpu` package, native sources AND the compiled
  `libtezhost.so` (built on the fly via `make -C native` when a toolchain
  is present), docs, examples, packaging metadata.
- minimal: the framework package only — no examples, no tools, no docs,
  native as source (built on first use by `ops/native.py`).
"""
from __future__ import annotations

import argparse
import io
import os
import subprocess
import sys
import tarfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# tools/ stays in minimal: the AM web controller imports swimlane/analyzer
# modules at request time, so they are framework, not extras
_MINIMAL_EXCLUDED_PKG_DIRS = ("examples", "models")
_SKIP_NAMES = ("__pycache__", ".pytest_cache")


def _walk_files(root: str, rel_base: str):
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in _SKIP_NAMES]
        for name in sorted(filenames):
            if name.endswith((".pyc", ".pyo")):
                continue
            full = os.path.join(dirpath, name)
            yield full, os.path.join(rel_base, os.path.relpath(full, root))


def _try_build_native() -> str | None:
    native_dir = os.path.join(_REPO, "tez_tpu", "native")
    so = os.path.join(native_dir, "libtezhost.so")
    try:
        subprocess.run(["make", "-C", native_dir], check=True,
                       capture_output=True)
    except Exception as e:  # noqa: BLE001 — toolchain-free hosts ship source-only
        # never ship a possibly-stale binary when the rebuild failed
        print(f"warning: native build failed ({e!r:.120}); "
              "assembly ships native sources only", file=sys.stderr)
        return None
    return so if os.path.exists(so) else None


def build(minimal: bool, out_dir: str) -> str:
    from tez_tpu.version import __version__
    # docs/ exists only in a source checkout (native sources ship inside
    # the wheel, so they do not distinguish the two)
    if not os.path.isdir(os.path.join(_REPO, "docs")):
        raise SystemExit(
            "tez-dist assembles from a source checkout (docs/, "
            f"pyproject.toml beside the package); {_REPO} lacks them — "
            "run it from the repository root")
    name = f"tez-tpu-{__version__}" + ("-minimal" if minimal else "")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, name + ".tar.gz")

    # full assemblies bundle a freshly built libtezhost.so; minimal ships
    # native as source only (built on first use by ops/native.py) — and a
    # stale committed .so must never ride along either assembly
    ship_so = (_try_build_native() is not None) if not minimal else False

    members: list[tuple[str, str]] = []
    pkg_root = os.path.join(_REPO, "tez_tpu")
    for full, rel in _walk_files(pkg_root, f"{name}/tez_tpu"):
        parts = os.path.relpath(full, pkg_root).split(os.sep)
        if minimal and parts[0] in _MINIMAL_EXCLUDED_PKG_DIRS:
            continue
        base = os.path.basename(full)
        if parts[0] == "native" and base.endswith((".so", ".tmp")) and \
                not (ship_so and base == "libtezhost.so"):
            continue
        members.append((full, rel))

    if not minimal:
        members.extend(_walk_files(os.path.join(_REPO, "docs"),
                                   f"{name}/docs"))
        readme = os.path.join(_REPO, "README.md")
        if os.path.exists(readme):
            members.append((readme, f"{name}/README.md"))
    pyproject = os.path.join(_REPO, "pyproject.toml")
    if os.path.exists(pyproject):
        members.append((pyproject, f"{name}/pyproject.toml"))

    with tarfile.open(out_path, "w:gz") as tf:
        for full, rel in members:
            tf.add(full, arcname=rel, recursive=False)
        manifest = "\n".join(sorted(rel for _, rel in members)) + "\n"
        info = tarfile.TarInfo(f"{name}/MANIFEST")
        data = manifest.encode()
        info.size = len(data)
        info.mtime = int(time.time())
        tf.addfile(info, io.BytesIO(data))
    return out_path


def main() -> int:
    parser = argparse.ArgumentParser(
        description="Build a tez-tpu distribution tarball (tez-dist analog)")
    parser.add_argument("--minimal", action="store_true",
                        help="framework-only assembly (tez-dist-minimal)")
    parser.add_argument("--out", default=os.path.join(_REPO, "dist"))
    args = parser.parse_args()
    path = build(args.minimal, args.out)
    print(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
