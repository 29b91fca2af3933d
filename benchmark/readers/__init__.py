"""One small reader per way of taking a per-layer metric from a run's
observations. A reader returns None when what it reads is absent, and the
harness then leaves the metric out of the line.

read(obs, args) -> float | None, where obs holds
  values       {name: number}   host-clock spans (s) and exact counts the driver kept
  trace        trace_reduce.reduce_trace()'s dict, or None without --trace 1
  traced_work  the driver's work records for the traced span
  dims, peaks  the configuration file and the device's peaks
"""
