"""Drive benchmarks/run.py with the program broken underneath, or with a
control in the program's place: both have to come out as not correct.

    python3 benchmarks/tests/faulty_run.py <fault> --workload ... --rehearse 2
    python3 benchmarks/tests/faulty_run.py control --workload ... --seed n [--rehearse 2]

Faults (the ones a cell of this benchmark can have; a training step that
returns its state unchanged has no counterpart in a DAG of batch work):

* ``half_batch``        half of the corpus's part files never reach the DAG;
* ``answer_altered``    one count altered where the summation task produces it;
* ``exchange_left_out`` (mesh cells) every chip keeps only the rows it
  produced for itself: nothing crosses the ICI exchange.

``control`` needs no chip and runs no window: it makes the cell's corpus from
the seed at the cell's own size, puts the plain reference's output, each of
the generator's guarantees broken in turn, where the program's would be, and
prints the numbers the comparison gives for each.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)


def load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


def plant_half_batch() -> None:
    from tez_tpu.examples import ordered_wordcount
    build = ordered_wordcount.build_dag

    def build_half(input_paths, output_path, **kwargs):
        files = sorted(os.path.join(d, f) for d in input_paths
                       for f in os.listdir(d))
        return build(files[::2], output_path, **kwargs)

    ordered_wordcount.build_dag = build_half


def plant_answer_altered() -> None:
    import numpy as np
    from tez_tpu.examples import ordered_wordcount
    run = ordered_wordcount.SumProcessor.run

    def run_altered(self, inputs, outputs):
        out = outputs["sorter"]
        get_writer = out.get_writer

        def writer_altered():
            writer = get_writer()
            write_batch = writer.write_batch

            def write_one_more(batch):
                # keys are big-endian counts: the first word's count + 1
                keys = np.array(batch.key_bytes, copy=True)
                if len(keys) >= 8 and keys[7] < 255:
                    keys[7] += 1
                    batch = type(batch)(keys, batch.key_offsets,
                                        batch.val_bytes, batch.val_offsets)
                write_batch(batch)

            writer.write_batch = write_one_more
            return writer

        out.get_writer = writer_altered
        return run(self, inputs, outputs)

    ordered_wordcount.SumProcessor.run = run_altered


def plant_exchange_left_out() -> None:
    from tez_tpu.parallel.coordinator import MeshExchangeCoordinator
    execute = MeshExchangeCoordinator._execute

    def kept_local(self, st):
        spans = dict(st.spans)
        out = []
        try:
            for c in range(st.num_consumers):
                st.spans = {c: spans[c]} if c in spans else {}
                out.append(execute(self, st)[c])
        finally:
            st.spans = spans
        return out

    MeshExchangeCoordinator._execute = kept_local


FAULTS = {"half_batch": plant_half_batch,
          "answer_altered": plant_answer_altered,
          "exchange_left_out": plant_exchange_left_out}


def control(run, argv) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    generator = run.load_module("generators", spec["config"]["generator"])
    params = run.corpus_params(spec, args.rehearse)
    workdir = tempfile.mkdtemp(prefix="tez_bench_control_")
    try:
        made = generator.generate(os.path.join(workdir, "corpus"), params,
                                  args.seed)
        readings = {}
        for broken in (None,) + tuple(generator.CONTROLS):
            out = os.path.join(workdir, f"out-{broken}")
            generator.reference_output(out, made["reference"], broken)
            numbers = generator.compare(out, made["reference"])
            readings[str(broken)] = {
                "correct": all(v <= generator.LIMITS[k]
                               for k, v in numbers.items()),
                "compared": numbers}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "records": made["records"], "controls": readings}),
          flush=True)
    return 0


def main() -> int:
    which, argv = sys.argv[1], sys.argv[2:]
    run = load_run()
    sys.path[:0] = [ROOT, BENCH]
    if which == "control":
        return control(run, argv)
    FAULTS[which]()
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
