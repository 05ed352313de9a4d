"""The on-chip benchmark of hydragnn_tpu: see README.md in this directory."""
import time

START = time.perf_counter()   # of the process, as near as Python lets us:
#                               set-up (`setup_s`) counts from here


def say(message: str) -> None:
    """A line for the reader of the log, with the seconds since START; the
    result is the LAST line."""
    print(f"[bench {time.perf_counter() - START:7.2f}s] {message}",
          flush=True)
