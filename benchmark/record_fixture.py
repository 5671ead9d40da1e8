"""Cut a recorded trace down to a test fixture.

    python3 benchmark/record_fixture.py <trace.xplane.pb> <runs> <out.events.json.gz>

Keeps the device op, async-op and module lines and the benchmark's host spans
from the start of the first run of the main program to the start of run
``runs + 1`` (or the end of the last), in the neutral form ``tracered`` reduces.
``tests/benchmark/`` pins the reduction's figures on such files, so that every
later PR computes the same number the same way.
"""

import sys

import tracered


def main(path: str, runs: str, out: str) -> None:
    planes = tracered.load_xplane(path)
    lo, hi = tracered.window_of(tracered.device_planes(planes)[0], int(runs))
    tracered.dump_fixture(planes, out, lo, hi + 1)      # the closing run's
    print(tracered.reduce_trace(tracered.load_fixture(out), int(runs)))


if __name__ == "__main__":
    main(*sys.argv[1:])
