"""Device time by round scope and idle gaps by program span
(``bench/scopes.py``), on plain tuples and on a tiny cell's chunk program."""
import pytest

from bench import common, scopes

PATH = "jit(chunk)/while/body/closed_call/{}/mul"


@pytest.mark.parametrize("component, scope", [
    ("vmap(transpose(jvp(local_step)))", "local_step"),
    ("vmap(local_step)", "local_step"),
    ("langevin_noise", "langevin_noise"),
    ("mix/vmap(encode)", "encode"),          # the innermost scope counts
    ("vmap(jit(fused_delta_pack))", None),
    ("transpose(jvp(local_steps))", None),   # a whole component, not a prefix
])
def test_scope_through_transform_wrappers(component, scope):
    assert scopes.scope_of(PATH.format(component)) == scope


HLO = """\
HloModule jit_chunk, entry_computation_layout={()->f32[]}

%fused_computation.7 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %multiply.1 = f32[4]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(chunk)/while/body/vmap(transpose(jvp(local_step)))/mul"}
}

%fused_computation.9 (param_0.2: f32[2,4], param_1.2: f32[4]) -> (f32[4], f32[]) {
  %param_0.2 = f32[2,4]{1,0} parameter(0)
  %param_1.2 = f32[4]{0} parameter(1)
  %slice.3 = f32[1,4]{1,0} slice(%param_0.2), slice={[0:1], [0:4]}
  %bitcast.3 = f32[4]{0} bitcast(%slice.3)
  %multiply.3 = f32[4]{0} multiply(%param_1.2, %param_1.2), metadata={op_name="jit(chunk)/while/body/langevin_noise/vmap()/mul"}
  %add.3 = f32[4]{0} add(%multiply.3, %bitcast.3), metadata={op_name="jit(chunk)/while/body/langevin_noise/add"}
  %add.4 = f32[4]{0} add(%add.3, %param_1.2), metadata={op_name="jit(chunk)/while/body/langevin_noise/add"}
  %constant.3 = f32[] constant(0)
  %reduce.3 = f32[] reduce(%add.4, %constant.3), dimensions={0}, to_apply=%add_f32, metadata={op_name="jit(chunk)/while/body/round_metrics/reduce_sum"}
  ROOT %tuple.3 = (f32[4]{0}, f32[]) tuple(%add.4, %reduce.3)
}

%wide.body (wide.param.0: (u32[], f32[2,4])) -> (u32[], f32[2,4]) {
  %wide.param.0 = (u32[], f32[2,4]{1,0}) parameter(0)
  %get-tuple-element.1 = u32[] get-tuple-element(%wide.param.0), index=0
  %get-tuple-element.2 = f32[2,4]{1,0} get-tuple-element(%wide.param.0), index=1
  %dynamic-update-slice.1 = f32[2,4]{1,0} dynamic-update-slice(%get-tuple-element.2, %get-tuple-element.2, %get-tuple-element.1, %get-tuple-element.1)
  ROOT %tuple.1 = (u32[], f32[2,4]{1,0}) tuple(%get-tuple-element.1, %dynamic-update-slice.1)
}

%wide.cond (wide.param.1: (u32[], f32[2,4])) -> pred[] {
  %wide.param.1 = (u32[], f32[2,4]{1,0}) parameter(0)
  %get-tuple-element.3 = u32[] get-tuple-element(%wide.param.1), index=0
  %constant.1 = u32[] constant(2)
  ROOT %compare.1 = pred[] compare(%get-tuple-element.3, %constant.1), direction=LT
}

ENTRY %body (p: f32[4]) -> (f32[4], f32[]) {
  %p = f32[4]{0} parameter(0)
  %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.7
  %custom-call.3 = f32[8]{0} custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk)/while/body/vmap(encode)/pallas_call"}
  %copy.2 = f32[8]{0} copy(%custom-call.3)
  %reshape.4 = f32[2,4]{1,0} reshape(%copy.2), metadata={op_name="jit(chunk)/while/body/decode/vmap(jit(unpack))/reshape"}
  %constant.5 = u32[] constant(0)
  %tuple.6 = (u32[], f32[2,4]{1,0}) tuple(%constant.5, %reshape.4)
  %while.7 = (u32[], f32[2,4]{1,0}) while(%tuple.6), condition=%wide.cond, body=%wide.body
  %get-tuple-element.8 = f32[2,4]{1,0} get-tuple-element(%while.7), index=1
  ROOT %fusion.9 = (f32[4]{0}, f32[]) fusion(%get-tuple-element.8, %p), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(chunk)/while/body/round_metrics/reduce_sum"}
}
"""


def test_hlo_text_parsed_to_ops():
    ops = scopes.parse_hlo(HLO)
    fusion = ops["fusion.9"]
    assert fusion.computation == "body" and fusion.opcode == "fusion"
    assert fusion.operands == ("get-tuple-element.8", "p")
    assert fusion.called == ("fused_computation.9",)
    assert fusion.elements == 5                  # a tuple's arrays together
    assert ops["while.7"].called == ("wide.cond", "wide.body")
    assert ops["reduce.3"].computation == "fused_computation.9"
    assert ops["copy.2"].op_name == ""


@pytest.mark.parametrize("op, shares", [
    ("fusion.7", {"local_step": 1.0}),         # from the op fused into it
    ("custom-call.3", {"encode": 1.0}),        # its own metadata
    # element work: langevin 4 + 4 + 4, the reduction its 4 inputs
    ("fusion.9", {"langevin_noise": 0.75, "round_metrics": 0.25}),
    # no metadata, as near to encode's kernel as to decode's reshape:
    # the layout change it carries out is the reshape's
    ("copy.2", {"decode": 1.0}),
    ("while.7", {"decode": 1.0}),              # nearest, the reshape first
    ("dynamic-update-slice.1", {"decode": 1.0}),   # its loop's scope
    ("compare.1", {"decode": 1.0}),
])
def test_op_shares(op, shares):
    got = scopes.op_shares(HLO)[op]
    assert got.keys() == shares.keys()
    for scope, share in shares.items():
        assert got[scope] == pytest.approx(share)


def test_op_without_any_scope_is_unscoped():
    text = HLO.replace('op_name="', 'op_type="')
    assert not any(scopes.op_shares(text).values())


def test_device_time_by_scope_and_the_unscoped_rest():
    shares = scopes.op_shares(HLO)
    op_time = {"fusion.7": 3.0, "custom-call.3": 2.0, "copy.2": 0.5,
               "dynamic-update-slice.1": 0.25, "fusion.9": 1.0,
               "convert.1": 0.125}
    by_scope = scopes.device_scopes(op_time, shares)
    assert by_scope["local_step"] == 3.0 and by_scope["encode"] == 2.0
    assert by_scope["decode"] == 0.75 and by_scope["mix"] == 0.0
    assert by_scope["langevin_noise"] == 0.75
    assert by_scope["round_metrics"] == 0.25
    # an op the program text does not hold
    assert by_scope[scopes.UNSCOPED] == 0.125
    assert sum(by_scope.values()) == pytest.approx(sum(op_time.values()))


def test_scope_metric_reads_ms_per_round_and_nothing_unscoped_only():
    ctx = {"trace": {"op_time": {"fusion.7": 3.0, "custom-call.3": 2.0,
                                 "fusion.9": 1.0}},
           "counts": {"rounds": 100}, "chunk_shares": scopes.op_shares(HLO)}
    assert scopes.train_scope_ms(ctx, ("local_step",)) == 30.0
    assert scopes.train_scope_ms(ctx, ("encode", "decode")) == 20.0
    assert scopes.train_scope_ms(ctx, ("langevin_noise",)) == 7.5
    assert scopes.train_scope_ms(ctx, ("mix",)) == 0.0
    # a program without round scopes, or a run without a trace, reads
    # nothing
    assert scopes.train_scope_ms(dict(ctx, chunk_shares=None),
                                 ("local_step",)) is None
    assert scopes.train_scope_ms(dict(ctx, trace={}), ("mix",)) is None


class _Lowered:
    """A lowered chunk whose compile records the cache-key setting."""

    def __init__(self, text):
        self.text, self.metadata_in_key = text, None

    def compile(self):
        import jax
        self.metadata_in_key = \
            jax.config.jax_compilation_cache_include_metadata_in_key
        return self

    def as_text(self):
        return self.text


def _tiny_cell():
    cfg = dict(common.find("configs", "lenet-radar"), input_hw=[32, 16],
               fc1=32, params=None)
    traffic = dict(common.find("traffic", "train-k10-ring"), nodes=3, pool=8,
                   batch=2, local_steps=2, bank_capacity=4, block=64,
                   ratio=0.05)
    return cfg, traffic


@pytest.mark.parametrize("text", [HLO, HLO.replace('op_name="', 'op_type="')],
                         ids=["scoped", "stale"])
def test_chunk_shares_keys_the_cache_with_metadata(monkeypatch, text):
    import jax

    from repro.train.engine import ScanRoundEngine
    lowered = _Lowered(text)
    monkeypatch.setattr(ScanRoundEngine, "lower_chunk",
                        lambda *a, **k: lowered)
    before = jax.config.jax_compilation_cache_include_metadata_in_key
    if text is HLO:
        assert scopes.chunk_shares(*_tiny_cell()) == scopes.op_shares(HLO)
    else:
        # an executable loaded with another program's metadata
        with pytest.raises(RuntimeError, match="no round scope"):
            scopes.chunk_shares(*_tiny_cell())
    assert lowered.metadata_in_key is True
    assert jax.config.jax_compilation_cache_include_metadata_in_key == before


def test_chunk_shares_of_a_program_without_lower_chunk(monkeypatch):
    from repro.train.engine import ScanRoundEngine
    monkeypatch.delattr(ScanRoundEngine, "lower_chunk")
    assert scopes.chunk_shares(*_tiny_cell()) is None


BENCH_SPANS = [("dispatch_chunk", 0, 100), ("step", 200, 300)]
PROGRAM_SPANS = [
    ("engine.dispatch", 0, 10), ("engine.sync_metrics", 10, 80),
    ("engine.histories", 80, 100),
    ("serve.step", 200, 300), ("serve.admit", 200, 240),
    ("serve.predict", 240, 250), ("serve.fetch", 250, 290),
]


@pytest.mark.parametrize("gap, name", [
    ((82, 98), "dispatch_chunk/engine.histories"),
    ((20, 85), "dispatch_chunk/engine.sync_metrics"),   # covers most of it
    ((205, 230), "step/serve.admit"),                   # innermost of two
    ((215, 260), "step/serve.admit"),                   # over three phases
    ((230, 260), "step/serve.step"),                    # no phase covers half
    ((291, 299), "step/serve.step"),                    # no phase under it
    ((120, 180), "untraced host"),                      # no span at all
])
def test_gap_named_by_innermost_program_span(gap, name):
    assert scopes.name_gaps([gap], BENCH_SPANS, PROGRAM_SPANS) == [name]


def test_gap_under_no_program_span_keeps_its_name():
    assert scopes.name_gaps([(150, 160), (95, 120)],
                            BENCH_SPANS + [("wait", 140, 199)],
                            PROGRAM_SPANS) == ["wait",
                                               "dispatch_chunk/engine.histories"]


def test_tiny_chunk_program_carries_every_round_scope():
    shares = scopes.chunk_shares(*_tiny_cell())
    assert set(scopes.ROUND_SCOPES) <= {s for op in shares.values()
                                        for s in op}
