"""Spill-scale OrderedWordCount bench: the 100 GB protocol's stage 1.

Reference scale story: PipelinedSorter multi-spill sort
(tez-runtime-library/.../sort/impl/PipelinedSorter.java:559), MergeManager
mem->disk cascade (.../orderedgrouped/MergeManager.java:387), io.sort.factor
batched merge (.../TezMerger.java:76).  This harness drives data >> span
budget through the FULL framework — DAG submission, producer span spills to
disk, shuffle fetch, consumer disk-cascade merge — and records the counters
that prove it (SPILLED_RECORDS, ADDITIONAL_SPILLS_BYTES_WRITTEN), with the
output verified against a streamed host golden.

High-cardinality corpus: zipfian draws over a --vocab-size vocabulary large
enough that the map-side combine cannot collapse the stream (combine is
DISABLED here anyway — the point is the raw spill path).

Usage:
    python -m tez_tpu.tools.spill_bench --mb 1024 --sort-mb 64 \
        --out chiprun_out/spill.json

chip_smoke.py drives the same DAG at the same size as its proof that the
main path runs on the chip; this tool adds the C++ proxy comparison.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np


def make_corpus(path: str, target_mb: int, vocab: int, seed: int = 0,
                part_bytes: int = 0) -> "tuple[int, np.ndarray]":
    """Zipfian corpus over w<id> words; returns (bytes, counts[vocab]).

    One file at `path`, or — with `part_bytes` — a directory `path` of
    part-NNNNN.txt files cut at line ends once they reach that size (the
    same bytes either way; a machine with a file-size limit refuses a single
    1 GB file)."""
    rng = np.random.default_rng(seed)
    width = len(str(vocab - 1))
    counts = np.zeros(vocab, dtype=np.int64)
    total = 0
    chunk_words = 1 << 20
    words_per_line = 8192
    if part_bytes:
        os.makedirs(path)
    parts = 0
    in_part = 0
    fh = None
    try:
        while total < target_mb << 20:
            ids = rng.zipf(1.2, chunk_words).astype(np.int64) % vocab
            counts += np.bincount(ids, minlength=vocab)
            chunk = np.char.add("w", np.char.zfill(
                ids.astype(f"U{width}"), width))
            for s in range(0, len(chunk), words_per_line):
                if fh is not None and part_bytes and in_part >= part_bytes:
                    fh.close()
                    fh = None
                if fh is None:
                    fh = open(os.path.join(path, f"part-{parts:05d}.txt")
                              if part_bytes else path, "w")
                    parts += 1
                    in_part = 0
                text = " ".join(chunk[s:s + words_per_line])
                fh.write(text)
                fh.write("\n")
                total += len(text) + 1
                in_part += len(text) + 1
    finally:
        if fh is not None:
            fh.close()
    return total, counts


def verify_output(out_dir: str, golden_counts: np.ndarray) -> int:
    """Streamed verification: parse w<id> words back to ids, compare the
    whole count vector (no gigantic dicts)."""
    got = np.zeros_like(golden_counts)
    n_lines = 0
    for name in sorted(os.listdir(out_dir)):
        if name.startswith(("_", ".")):
            continue
        with open(os.path.join(out_dir, name)) as fh:
            for line in fh:
                if not line.strip():
                    continue
                w, c = line.rsplit(None, 1)
                got[int(w[1:])] += int(c)
                n_lines += 1
    if not np.array_equal(got, golden_counts):
        raise ValueError(f"output mismatch: "
                         f"{int((got != golden_counts).sum())} words differ")
    return n_lines


def run(target_mb: int, vocab: int, sort_mb: int, engine: str,
        parallelism: int, pipelined: bool = False) -> dict:
    from tez_tpu.client.tez_client import TezClient
    from tez_tpu.examples import ordered_wordcount
    td = tempfile.mkdtemp(prefix="tez_spill_")
    try:
        corpus = os.path.join(td, "corpus.txt")
        t0 = time.time()
        nbytes, golden = make_corpus(corpus, target_mb, vocab)
        gen_s = time.time() - t0
        conf = {"tez.staging-dir": os.path.join(td, "stg"),
                "tez.runtime.sorter.class": engine,
                "tez.runtime.io.sort.mb": sort_mb,
                "tez.runtime.tpu.host.spill.dir": os.path.join(td, "spill")}
        if pipelined:
            # one event per spilled span, NO producer final merge
            # (reference: tez.runtime.pipelined-shuffle.enabled)
            conf["tez.runtime.pipelined-shuffle.enabled"] = True
        out_dir = os.path.join(td, "out")
        t0 = time.time()
        with TezClient.create("spill-bench", conf) as client:
            dag = ordered_wordcount.build_dag(
                [corpus], out_dir, tokenizer_parallelism=parallelism,
                summation_parallelism=parallelism, sorter_parallelism=1,
                combine=False, tokenizer_mode="vector")
            dag_client = client.submit_dag(dag)
            status = dag_client.wait_for_completion()
            final = dag_client.get_dag_status(with_counters=True)
        wall = time.time() - t0
        assert status.state.name == "SUCCEEDED", status
        counters: dict = {}
        snap = getattr(final, "counters", None)
        if snap is not None:
            for group in snap.to_dict().values():
                for name in ("SPILLED_RECORDS", "SHUFFLE_BYTES",
                             "ADDITIONAL_SPILLS_BYTES_WRITTEN",
                             "ADDITIONAL_SPILLS_BYTES_READ",
                             "OUTPUT_RECORDS", "REDUCE_INPUT_RECORDS"):
                    if name in group:
                        counters[name] = counters.get(name, 0) + group[name]
        t0 = time.time()
        distinct = verify_output(out_dir, golden)
        verify_s = time.time() - t0

        # EXTERNAL baseline (BASELINE.md protocol): the reference-semantics
        # C++ OrderedWordCount proxy with COMBINE OFF on the identical
        # corpus — every (word,1) record through span sort + heap merges,
        # the exact machinery this bench stresses.  All-RAM and
        # single-pass (no spill I/O), which makes it a CONSERVATIVE
        # baseline: the reference would also pay disk at this scale.
        from tez_tpu.ops.native import owc_proxy_counts
        proxy_s, counts_by_word = owc_proxy_counts(
            corpus, parallelism, parallelism, combine=False)
        got = np.zeros_like(golden)
        for w, cnt in counts_by_word.items():
            got[int(w[1:])] += cnt
        if not np.array_equal(got, golden):
            raise RuntimeError(
                "owc_proxy(no-combine) output mismatch vs golden")
        import jax
        from tez_tpu.ops.device import backend_platform
        from tez_tpu.ops.sorter import resolve_engine
        resolved = resolve_engine(engine)
        backend = backend_platform()
        return {
            "metric": (f"OrderedWordCount spill-scale E2E ({target_mb} MB "
                       f"input, vocab {vocab}, io.sort.mb={sort_mb}, "
                       f"combine OFF, {'pipelined, ' if pipelined else ''}"
                       f"engine={engine}->{resolved} on "
                       f"jax backend={backend}, output verified "
                       f"vs streamed host golden)"),
            "engine_requested": engine,
            "engine_resolved": resolved,
            "jax_backend": backend,
            "device_kind": jax.devices()[0].device_kind,
            "value": round(nbytes / 1e6 / wall, 2),
            "unit": "MB/s",
            "vs_baseline": round(proxy_s / wall, 3),
            "baseline": (f"C++ reference-semantics OrderedWordCount proxy, "
                         f"combine off, all-RAM single-pass (conservative): "
                         f"{proxy_s:.1f}s on the same corpus"),
            "wall_seconds": round(wall, 1),
            "corpus_gen_seconds": round(gen_s, 1),
            "verify_seconds": round(verify_s, 1),
            "distinct_words": distinct,
            "counters": counters,
        }
    finally:
        shutil.rmtree(td, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mb", type=int, default=1024)
    ap.add_argument("--vocab-size", type=int, default=2_000_000)
    ap.add_argument("--sort-mb", type=int, default=64)
    ap.add_argument("--engine", default="auto",
                    help="auto|device|host sorter engine (auto = device "
                         "kernels on an accelerator backend, host kernels "
                         "when JAX_PLATFORMS=cpu was asked for)")
    ap.add_argument("--parallelism", type=int, default=4)
    ap.add_argument("--pipelined", action="store_true",
                    help="one event per spilled span; no producer final "
                         "merge (tez.runtime.pipelined-shuffle.enabled)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    rec = run(args.mb, args.vocab_size, args.sort_mb, args.engine,
              args.parallelism, pipelined=args.pipelined)
    line = json.dumps(rec)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    spilled = rec["counters"].get("SPILLED_RECORDS", 0)
    if spilled <= 0:
        print("WARNING: no spills — raise --mb or lower --sort-mb",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
