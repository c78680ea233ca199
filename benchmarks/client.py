#!/usr/bin/env python3
"""The load generator: streaming requests over HTTP NDJSON, timed on the
client's clock (``time.monotonic``: one clock for every process of a
machine).  ``stream_request`` is copied from ``chip_smoke._post`` and
``tools/serve_loadtest._gap_stream_client`` (originals listed in
PERF.md's Open questions), returning what it saw instead of tallying.

Run as a program it is the closed loop, in a process of its own that
never touches JAX, so that the clients' threads do not share the
server's interpreter lock:

    python3 benchmarks/client.py HOST PORT PATH TRAFFIC.json VOCAB SEED

``clients`` threads each send the stream's next request when their last
completes, until a line arrives on standard input; then the answers
still coming are cut and the records go to standard output, one JSON
object a line."""

import http.client
import json
import os
import socket
import sys
import threading
import time


def stream_request(host, port, path, prompt, max_new, timeout=300.0,
                   cut=None, live=None):
    """POST one greedy streaming generate request.  Returns a dict:
    ``sent`` (monotonic time the request left), ``first`` (time of the
    first token line, None if none came), ``line_times`` and
    ``line_tokens`` (time of every token line and how many tokens it
    carried), ``streamed`` (the tokens of those lines, in order: what
    the answer had said when it was cut), ``done`` (time of the done
    line, None if it never came),
    ``result`` (the done line's full token list), ``phases`` (the
    engine's queue/prefill/decode milliseconds from the done line),
    ``outcome``: 'ok', 'cut' (the load generator was told to stop while
    the answer was still coming: ``cut`` is set), or what went wrong.
    ``live`` is a set the open socket sits in while it is read, so that
    the one who sets ``cut`` can shut it down."""
    body = json.dumps({"input": prompt,
                       "generate": {"max_new": max_new, "stream": True}})
    rec = {"sent": time.monotonic(), "first": None, "line_times": [],
           "line_tokens": [], "streamed": [], "done": None, "result": None,
           "phases": None, "outcome": "error"}
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    sock = None
    try:
        conn.request("POST", path, body,
                     {"Content-Type": "application/json"})
        sock = conn.sock    # the response takes it over: keep a handle
        if live is not None:
            live.add(sock)
        if cut is not None and cut.is_set():
            return rec          # told to stop as this one left
        resp = conn.getresponse()
        if resp.status != 200:
            resp.read()
            rec["outcome"] = "http_%d" % resp.status
            return rec
        while True:
            raw = resp.fp.readline()
            if not raw:
                rec["outcome"] = "truncated"
                break
            now = time.monotonic()
            msg = json.loads(raw)
            if "tokens" in msg:
                if rec["first"] is None:
                    rec["first"] = now
                rec["line_times"].append(now)
                rec["line_tokens"].append(len(msg["tokens"]))
                rec["streamed"].extend(msg["tokens"])
            if "error" in msg:
                rec["outcome"] = "stream_error"
                break
            if msg.get("done"):
                rec.update(done=now, result=msg.get("result"),
                           phases=msg.get("phases"), outcome="ok")
                break
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec["outcome"] = "error:%s" % type(e).__name__
    finally:
        if live is not None:
            live.discard(sock)
        conn.close()
    if cut is not None and cut.is_set() and rec["outcome"] != "ok":
        rec["outcome"] = "cut"
    return rec


def closed_loop(host, port, path, stream, clients, stop):
    """``clients`` threads, each sending the stream's next request when
    its last completes, until ``stop`` is set; the answers still coming
    then are cut (their connections shut down: a request of some
    hundreds of tokens outlasts any window, and what it has streamed so
    far is in its record).  Returns the records, each with its
    ``prompt`` and ``max_new``."""
    lock, records, live = threading.Lock(), [], set()

    def one_client():
        while not stop.is_set():
            with lock:
                prompt, max_new = next(stream)
            rec = stream_request(host, port, path, prompt, max_new,
                                 cut=stop, live=live)
            rec.update(prompt=prompt, max_new=max_new)
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=one_client, daemon=True)
               for _ in range(clients)]
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
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import traffic
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
