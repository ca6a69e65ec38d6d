"""Open loop: creates go out at due times fixed before the run
(``traffic.arrival_offsets``: ``rate_per_s``, optional ``burst``), whatever
the broker does; latency counts from the due time. The window opens after
``preroll_s`` seconds of the same arrivals."""

import threading
import time

from zbench import traffic


def run(gen, emit) -> tuple:
    spec = gen.spec
    seconds, preroll = spec["seconds"], spec["preroll_s"]
    offsets = traffic.arrival_offsets(
        spec["rate_per_s"], preroll + seconds, spec["seed"], spec.get("burst")
    )
    t0 = time.monotonic() + 0.05
    start = t0 + preroll
    lock = threading.Lock()
    cursor = [0]

    def sender() -> None:
        while True:
            with lock:
                i = cursor[0]
                cursor[0] += 1
            if i >= len(offsets):
                return
            due = t0 + offsets[i]
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            gen.create(due=due)

    threads = [
        threading.Thread(target=sender, daemon=True) for _ in range(spec["sender_threads"])
    ]
    for t in threads:
        t.start()
    time.sleep(max(0.0, start - time.monotonic()))
    emit("window_start", at=start)
    time.sleep(max(0.0, start + seconds - time.monotonic()))
    end = start + seconds
    emit("window_end", at=end)
    for t in threads:
        t.join(spec["request_timeout_ms"] / 1000 + 5)
    gen.stopping = True
    return start, end
