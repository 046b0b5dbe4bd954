"""TSST4 compressed columnar blocks (PAPERS.md arxiv 2506.10092:
keep data compressed through the scan, decode only what the aggregate
needs).

- codecs.py: self-describing per-block codecs (delta-of-delta
  timestamps + XOR floats / zigzag int deltas, zlib, verbatim) over
  sstable record bytes — vectorized numpy encode/decode with a
  write-time round-trip self-check.
- kernels.py: batched JAX decode and the fused decode-plus-aggregate
  stage (the decoded column lives only inside one XLA program).
- fused.py: the query-side block source — coverage checks that decide
  when a range can be served straight from compressed blocks, and the
  seek or walk that finds which.
- devcache.py: decoded blocks kept on the device, a block a slab row.
"""
