"""The on-chip benchmark's harness: specs, cell runners, counts and the trace
reduction. Nothing here is imported by the program under test."""
