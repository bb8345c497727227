"""TPC-H data and queries owned by the benchmark."""
