"""``python3 -m bench --workload <cell> --seed <n> --seconds <s> --trace <0|1>``"""

import time

T_START = time.perf_counter()  # set-up is counted from here: imports included

import sys  # noqa: E402

from bench.harness import main  # noqa: E402

sys.exit(main(sys.argv[1:], t_start=T_START))
