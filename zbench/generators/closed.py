"""Closed loop: ``in_flight`` instances are kept created and not yet
completed; a completion seen frees a slot and a new create goes out. The
window opens after one full turnover of the in-flight set."""

import threading
import time


def run(gen, emit) -> tuple:
    spec = gen.spec
    n, seconds = spec["in_flight"], spec["seconds"]
    base = gen.completed

    def sender() -> None:
        while not gen.stopping:
            if gen.slots.acquire(timeout=0.05) and not gen.stopping:
                gen.create()

    threads = [
        threading.Thread(target=sender, daemon=True) for _ in range(spec["sender_threads"])
    ]
    for t in threads:
        t.start()
    for _ in range(n):
        gen.slots.release()
    deadline = time.monotonic() + spec["turnover_timeout_s"]
    while gen.completed - base < n:
        if time.monotonic() > deadline:
            raise SystemExit("the in-flight set did not turn over")
        time.sleep(0.002)
    start = time.monotonic()
    emit("window_start", at=start)
    time.sleep(max(0.0, start + seconds - time.monotonic()))
    gen.stopping = True
    end = time.monotonic()
    emit("window_end", at=end)
    for t in threads:
        t.join(5)
    return start, end
