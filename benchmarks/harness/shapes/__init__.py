"""One module a record shape, found by the name a configuration gives
(`record_shape`); `http_logs` is `harness/corpus.py` itself."""
