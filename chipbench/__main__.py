import time

T_PROCESS_START = time.time()  # before any heavy import: set-up starts here

from chipbench.run import main  # noqa: E402

raise SystemExit(main(T_PROCESS_START))
