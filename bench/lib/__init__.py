"""The benchmark's shared machinery: deployments, traffic, the open-loop
source, the plain reference, spans, the trace reduction, peaks and the
kernel byte count."""
