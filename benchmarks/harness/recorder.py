"""Raw samples without touching the program: the server takes `metrics=`, and
this subclass of its ServingMetrics keeps every sample with the time it was
recorded at, beside the bucketed histograms (which the benchmark never
reads). Called from the server's own thread; appends are atomic."""
import time

from deeplearning4j_tpu.serving.metrics import ServingMetrics


class Recorder(ServingMetrics):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.ttft = []          # (t, ms): first token at t, ms after submit
        self.inter_token = []   # (t, ms): a later token at t
        self.requests = []      # (t, total_ms, tokens): completion at t
        self.occupancy = []     # (t, active slots)

    def record_ttft(self, ms):
        self.ttft.append((time.monotonic(), float(ms)))
        super().record_ttft(ms)

    def record_inter_token(self, ms):
        self.inter_token.append((time.monotonic(), float(ms)))
        super().record_inter_token(ms)

    def record_request(self, total_ms, queue_wait_ms=None, tokens=None,
                       deadline_met=None):
        self.requests.append((time.monotonic(), float(total_ms), tokens))
        super().record_request(total_ms, queue_wait_ms, tokens, deadline_met)

    def record_occupancy(self, active, slots):
        self.occupancy.append((time.monotonic(), int(active)))
        super().record_occupancy(active, slots)

    def counters(self, keys):
        return {k: self.count_value(k) for k in keys}
