import time

T0 = time.perf_counter()  # before any heavy import: set-up starts here

import sys  # noqa: E402

from chipbench.runner import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(T0))
