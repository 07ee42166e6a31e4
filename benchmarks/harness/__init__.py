"""The benchmark's harness: knows no cell, configuration or metric by
name; finds each by the names in BENCHMARK.json."""
