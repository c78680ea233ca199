#!/usr/bin/env python3
"""The closed loop of ``client.py`` with one repair: a request that
``client.stream_request`` hands back with its bare initial outcome
``"error"`` while the stop is set was cut while it was leaving (the
stop landed between the loop's check and the check after
``conn.request``), not failed, and is recorded ``"cut"``.  A real
failure never reads bare ``"error"`` (it reads ``error:<Type>``,
``http_<status>``, ``truncated`` or ``stream_error``) and stays
failed.  ``client.py`` itself is not touched (PERF.md section 7).

And one order: the clients start ``START_GAP_S`` apart, so that their
first requests reach the server in the stream's own order (ten threads
started at once race for it, and which sizes sit in the slots is the
work: ``traffic.py``).

    python3 benchmarks/client_closed.py HOST PORT PATH TRAFFIC.json VOCAB SEED
"""

import json
import os
import socket
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from benchmarks import client, traffic     # noqa: E402


#: seconds between one client's first request and the next one's
START_GAP_S = 0.05


def closed_loop(host, port, path, stream, clients, stop,
                request=client.stream_request):
    """``client.closed_loop`` with the repair above; ``request`` is the
    tests' seam."""
    lock, records, live = threading.Lock(), [], set()

    def one_client(nth):
        stop.wait(nth * START_GAP_S)
        while not stop.is_set():
            with lock:
                prompt, max_new = next(stream)
            rec = request(host, port, path, prompt, max_new, cut=stop,
                          live=live)
            if rec["outcome"] == "error" and stop.is_set():
                rec["outcome"] = "cut"
            rec.update(prompt=prompt, max_new=max_new)
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=one_client, args=(nth,),
                                daemon=True)
               for nth in range(clients)]
    for th in threads:
        th.start()
    stop.wait()
    for sock in list(live):
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass                  # it closed by itself meanwhile
    for th in threads:
        th.join()
    return records


def main(argv):
    host, port, path, traffic_file, vocab, seed = argv
    with open(traffic_file) as f:
        tf = json.load(f)
    stop = threading.Event()
    threading.Thread(target=lambda: (sys.stdin.readline(), stop.set()),
                     daemon=True).start()
    records = closed_loop(host, int(port), path,
                          traffic.request_stream(tf, int(vocab), int(seed)),
                          int(tf["clients"]), stop)
    for rec in records:
        sys.stdout.write(json.dumps(rec) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
