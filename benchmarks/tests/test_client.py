"""The load generator against a slow stand-in server: answers still
coming when it is told to stop are cut at once and keep what they had
streamed."""

import http.server
import itertools
import json
import threading
import time

from benchmarks import client


class SlowStream(http.server.BaseHTTPRequestHandler):
    """One token line every 20 ms, fifty of them, then the done line."""

    def do_POST(self):
        body = json.loads(self.rfile.read(
            int(self.headers["Content-Length"])))
        self.send_response(200)
        self.end_headers()
        try:
            for i in range(50):
                self.wfile.write(
                    (json.dumps({"tokens": [i, i]}) + "\n").encode())
                self.wfile.flush()
                time.sleep(0.02)
            self.wfile.write((json.dumps(
                {"done": True, "result": body["input"] + [0] * 100})
                + "\n").encode())
        except OSError:
            pass                      # the client cut the stream

    def log_message(self, *args):
        pass


def test_answers_still_coming_at_the_stop_are_cut():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), SlowStream)
    server.daemon_threads = True
    threading.Thread(target=server.serve_forever, daemon=True).start()
    stop = threading.Event()
    got = []
    loop = threading.Thread(target=lambda: got.extend(client.closed_loop(
        "127.0.0.1", server.server_address[1], "/", itertools.repeat(
            ([1, 2, 3], 100)), 4, stop)))
    loop.start()
    time.sleep(0.3)
    t0 = time.monotonic()
    stop.set()
    loop.join(timeout=5.0)
    took = time.monotonic() - t0
    server.shutdown()
    server.server_close()
    assert not loop.is_alive()
    assert took < 0.5                 # a whole answer takes 1 s
    assert len(got) == 4
    for rec in got:
        assert rec["outcome"] == "cut" and rec["done"] is None
        assert 5 <= len(rec["line_times"]) <= 25
        assert rec["line_tokens"] == [2] * len(rec["line_times"])
        assert rec["streamed"] == [i for i in range(
            len(rec["line_times"])) for _ in range(2)]
        assert rec["first"] == rec["line_times"][0] > rec["sent"]
