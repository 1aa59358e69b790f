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
