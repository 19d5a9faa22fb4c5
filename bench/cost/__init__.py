"""Operations and bytes the algorithms need, from shapes and live lengths.

The benchmark's own arithmetic (no program code): per kernel call in
bench/cost/paged_attention.py, per token of the model in
bench/cost/model.py.
"""
