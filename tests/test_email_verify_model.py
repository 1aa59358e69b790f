"""EmailVerify family end-to-end (mini params, twitter reset regex)."""

import pytest

from zkp2p_tpu.inputs.email import generate_email_verify_inputs, make_test_key, make_twitter_email
from zkp2p_tpu.models.email_verify import EmailVerifyParams, build_email_verify


@pytest.mark.slow
def test_email_verify_twitter_end_to_end():
    params = EmailVerifyParams(max_header_bytes=256, max_body_bytes=128)
    cs, lay = build_email_verify(params)
    key = make_test_key(1)
    email = make_twitter_email(key, handle="zk_pranker")
    inputs = generate_email_verify_inputs(email, key.n, params, lay)
    w = cs.witness(inputs.public_signals, inputs.seed)
    cs.check_witness(w)
    # revealed handle word: 'zk_pran' packed LE in word 0
    word0 = inputs.public_signals[params.k]
    assert word0 == sum(b << (8 * i) for i, b in enumerate(b"zk_pran"))

    # tampered reveal -> unsatisfied
    bad = list(inputs.public_signals)
    bad[params.k] += 1
    w_bad = cs.witness(bad, inputs.seed)
    with pytest.raises(AssertionError):
        cs.check_witness(w_bad)


@pytest.mark.slow
def test_email_verify_body_hash_idx_cannot_point_elsewhere():
    """Soundness regression: body_hash_idx must be tied
    to the bh= regex match — same attack as the venmo model's
    test_body_hash_idx_cannot_point_elsewhere.  The shift consumes the
    regex reveal mask (zero outside the match), so pointing the idx at
    other base64-alphabet header bytes breaks a constraint."""
    params = EmailVerifyParams(max_header_bytes=256, max_body_bytes=128)
    cs, lay = build_email_verify(params)
    key = make_test_key(1)
    email = make_twitter_email(key, handle="zk_pranker")
    inputs = generate_email_verify_inputs(email, key.n, params, lay)
    seed = dict(inputs.seed)
    honest_idx = seed[lay.body_hash_idx]
    seed[lay.body_hash_idx] = max(0, honest_idx - 30)
    w_bad = cs.witness(inputs.public_signals, seed)
    with pytest.raises(AssertionError):
        cs.check_witness(w_bad)


PUBLISHED = {"max_header_bytes": 1024, "max_body_bytes": 1536, "n": 121, "k": 17}  # email.circom:222


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_defaults_and_the_benchmarks_configuration_are_the_published_size(key):
    """`EmailVerifyParams()` IS `EmailVerify(1024, 1536, 121, 17)`, and the
    cell runs exactly that: a CI shape can never slip into it."""
    import dataclasses
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "configs", "email-1024-1536.json")) as f:
        config = json.load(f)
    assert dataclasses.asdict(EmailVerifyParams())[key] == PUBLISHED[key]
    assert config[key] == PUBLISHED[key] and config["source_sizes"][key] == PUBLISHED[key]
    assert config["reduced"] == [] and EmailVerifyParams().reveal_len == 21


@pytest.mark.slow
def test_the_published_size_is_2136048_constraints_on_a_2_22_domain():
    """The registry's flagship spec, through the same audit gate as the CI shape."""
    from zkp2p_tpu.models import registry
    from zkp2p_tpu.snark.groth16 import domain_size_for

    cs, rep = registry.audited("email_verify-full")
    assert rep["unwaived"] == 0, rep["findings"][:5]
    assert cs.num_constraints == 2_136_048 and cs.num_public == 20
    assert domain_size_for(cs) == 1 << 22
