"""Share of the traced window (%) in which the device was idle while the
engine was inside the decode call (the program's ``engine.decode`` span),
in the sLSTM cell. The reader of ``decode_idle_share.latency``, whose
entry and tests hold it to ``gru-jet.latency`` alone."""
from harness import spec

_SHARED = spec.metric_reader("decode_idle_share.latency")
SPANS = _SHARED.SPANS
read = _SHARED.read
