"""Percentile and tpot arithmetic on fixed samples."""

from benchlib import client, stats


def test_percentile_matches_linear_interpolation():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 95) == 48
    assert stats.percentile(xs, 0) == 10 and stats.percentile(xs, 100) == 50
    assert stats.percentile([7], 95) == 7
    assert stats.percentile([], 95) is None


def test_tpot_is_per_request_after_the_first_token():
    assert stats.tpot_ms(1.0, 1.8, 9) == 100.0
    assert stats.tpot_ms(1.0, 1.0, 1) is None


def test_tokens_in_window_counts_only_arrivals_inside():
    chunks = [(0.9, 8), (1.0, 8), (1.5, 8), (2.0, 8)]
    assert stats.tokens_in_window(chunks, 1.0, 2.0) == 16


def test_iqr_share_uses_statistics_quantiles():
    v = [100, 101, 102, 103, 104, 105]
    assert abs(stats.iqr_share(v) - (104.25 - 100.75) / 102.5) < 1e-12


def test_sse_token_count_without_json_parse():
    line = (b'data: {"id": "x", "choices": [{"index": 0, "text": "ab", '
            b'"token_ids": [5, 17, 300]}]}\n')
    assert client.count_token_ids(line) == 3
    assert client.count_token_ids(b'data: {"choices": [{"text": ""}]}') == 0
    assert client.count_token_ids(b'data: {"token_ids": [9]}') == 1
