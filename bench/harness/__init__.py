"""The benchmark's harness: the served path it drives, the traffic
generator, the references, and the reduction from traces and spans to
metrics. Nothing here is imported by the program under test."""
