"""Record the small traces ``test_trace_reduce.py`` checks the reduction on.

    python3 -m chipbench.tests.record_fixture bert_toy.mlm_toy 3      # one chip
    python3 -m chipbench.tests.record_fixture bert_toy.mlm_toy_dp4 2  # four

Run on the chip (a CPU trace has no device plane): a fixture cell of toy size
goes through the same ``run_cell`` as the real ones, that many traced steps
are kept, and the gzipped ``.xplane.pb`` lands in ``chiprun_out/chipbench/`` to
be copied to ``chipbench/tests/fixtures/traces/`` by hand.
"""

import gzip
import json
import shutil
import sys

from chipbench import run
from chipbench.catalog import ROOT, Catalog

FIXTURES = ROOT / "chipbench" / "tests" / "fixtures"


def main(workload, steps):
    run.TRACED_STEPS = steps
    result = run.run_cell(workload, seed=0, seconds=1.0, trace=True,
                          catalog=Catalog(FIXTURES / "benchmark.json"),
                          peaks=json.loads(
                              (ROOT / "chipbench" / "peaks.json").read_text()),
                          keep_trace=True)
    trace, = sorted((run.OUT_DIR / "trace" / workload).rglob("*.xplane.pb"))
    out = run.OUT_DIR / f"{workload}.xplane.pb.gz"
    with open(trace, "rb") as src, gzip.open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(run.OUT_DIR / "trace" / workload)
    print(f"{out}: {out.stat().st_size} bytes gzipped")
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
