/* secp256k1 ECDSA verification for the host lanes of a mixed committee,
 * a batch a call.
 *
 * A commit of BASELINE config 5 carries a hundred secp256k1 signatures
 * among its 10,000; they are the host's by design (crypto/batch.
 * HostLanesVerifier) and were verified one OpenSSL call a lane, in
 * OpenSSL's generic prime-curve code: 0.5 ms a lane, 43% of the call for
 * 1% of its signatures (PERF.md section 6, PR 49; 0.1 ms a lane here).
 * This file is that verification written for this one curve, as upstream's
 * crypto/secp256k1/secp256k1.go takes btcec's: the field mod
 * p = 2^256 - 0x1000003D1 in four 64-bit limbs folded by the prime's own
 * form, Jacobian points on y^2 = x^3 + 7, and u1*G + u2*Q in one
 * interleaved wNAF pass (a static affine table of G's odd multiples,
 * eight Jacobian odd multiples of Q a lane). Public data only: nothing
 * here is constant-time, and nothing need be.
 *
 * The rules are crypto/keys.Secp256k1PubKey.verify_signature's, lane by
 * lane: 0 < r < n, 0 < s <= n / 2 (low s), the key's prefix 2 or 3 with
 * x < p and x^3 + 7 a square, e = the SHA-256 digest mod n,
 * R = e/s * G + r/s * Q refused at infinity, accepted iff
 * R.x mod n = r. tests/test_secp256k1_native.py holds it to OpenSSL and
 * to chipbench/reference_mixed.verify_secp256k1 on every one of them.
 *
 * One thread: a hundred lanes take 10 ms on the chip's host. A team
 * over them takes 1.2 and the commit reads the same, because the call
 * then waits for the device's kernels instead (PERF.md section 6,
 * PR 49); and it would wake the workers sha512_batch.c's
 * PARALLEL_MIN_BATCH keeps asleep.
 *
 * Needs a 128-bit integer type. Where the compiler has none the entry
 * point still links (the library's other translation units build as
 * before) and returns 0: Python then keeps the OpenSSL path.
 */

#include <stdint.h>
#include <string.h>

#ifdef __SIZEOF_INT128__

typedef unsigned __int128 u128;
typedef uint64_t u256[4]; /* little-endian limbs */

/* --- 256-bit integers ------------------------------------------------------ */

static uint64_t add4(uint64_t *r, const uint64_t *a, const uint64_t *b) {
  u128 c = 0;
  for (int i = 0; i < 4; i++) {
    c += (u128)a[i] + b[i];
    r[i] = (uint64_t)c;
    c >>= 64;
  }
  return (uint64_t)c;
}

static uint64_t sub4(uint64_t *r, const uint64_t *a, const uint64_t *b) {
  uint64_t borrow = 0;
  for (int i = 0; i < 4; i++) {
    u128 d = (u128)a[i] - b[i] - borrow;
    r[i] = (uint64_t)d;
    borrow = (uint64_t)(d >> 64) & 1;
  }
  return borrow;
}

static int ge4(const uint64_t *a, const uint64_t *b) {
  for (int i = 3; i >= 0; i--)
    if (a[i] != b[i]) return a[i] > b[i];
  return 1;
}

static int is_zero4(const uint64_t *a) { return (a[0] | a[1] | a[2] | a[3]) == 0; }

static int eq4(const uint64_t *a, const uint64_t *b) {
  return ((a[0] ^ b[0]) | (a[1] ^ b[1]) | (a[2] ^ b[2]) | (a[3] ^ b[3])) == 0;
}

static void load_be(uint64_t *r, const uint8_t *b) {
  for (int i = 0; i < 4; i++) {
    uint64_t v = 0;
    for (int j = 0; j < 8; j++) v = (v << 8) | b[(3 - i) * 8 + j];
    r[i] = v;
  }
}

static void mul4(uint64_t t[8], const uint64_t *a, const uint64_t *b) {
  memset(t, 0, 8 * sizeof(uint64_t));
  for (int i = 0; i < 4; i++) {
    uint64_t carry = 0;
    for (int j = 0; j < 4; j++) {
      u128 m = (u128)a[i] * b[j] + t[i + j] + carry;
      t[i + j] = (uint64_t)m;
      carry = (uint64_t)(m >> 64);
    }
    t[i + 4] = carry;
  }
}

/* --- the field: integers mod p, always held below p ----------------------- */

static const u256 FP = {0xFFFFFFFEFFFFFC2FULL, ~0ULL, ~0ULL, ~0ULL};
#define FOLD 0x1000003D1ULL /* 2^256 mod p */

static void fe_add(uint64_t *r, const uint64_t *a, const uint64_t *b) {
  if (add4(r, a, b) || ge4(r, FP)) sub4(r, r, FP);
}

static void fe_sub(uint64_t *r, const uint64_t *a, const uint64_t *b) {
  if (sub4(r, a, b)) add4(r, r, FP);
}

static void fe_neg(uint64_t *r, const uint64_t *a) {
  if (is_zero4(a))
    memset(r, 0, sizeof(u256));
  else
    sub4(r, FP, a);
}

/* An eight-limb product mod p: the high half times 2^256 mod p onto the
 * low half, the 34 bits that leaves above 2^256 once more, and at most
 * one p off. */
static void fe_reduce(uint64_t *r, const uint64_t t[8]) {
  uint64_t l[4];
  u128 acc = 0;
  for (int i = 0; i < 4; i++) {
    acc += (u128)t[4 + i] * FOLD + t[i];
    l[i] = (uint64_t)acc;
    acc >>= 64;
  }
  acc = acc * FOLD + l[0];
  r[0] = (uint64_t)acc;
  acc >>= 64;
  for (int i = 1; i < 4; i++) {
    acc += l[i];
    r[i] = (uint64_t)acc;
    acc >>= 64;
  }
  if (acc) { /* wrapped past 2^256: what is left is under 2^67 */
    acc = (u128)r[0] + FOLD;
    r[0] = (uint64_t)acc;
    acc >>= 64;
    for (int i = 1; i < 4 && acc; i++) {
      acc += r[i];
      r[i] = (uint64_t)acc;
      acc >>= 64;
    }
  }
  if (ge4(r, FP)) sub4(r, r, FP);
}

static void fe_mul(uint64_t *r, const uint64_t *a, const uint64_t *b) {
  uint64_t t[8];
  mul4(t, a, b);
  fe_reduce(r, t);
}

static void fe_sqr(uint64_t *r, const uint64_t *a) { fe_mul(r, a, a); }

static void fe_sqr_n(uint64_t *r, const uint64_t *a, int n) {
  fe_sqr(r, a);
  while (--n) fe_sqr(r, r);
}

/* A square root of a where it has one: a^((p + 1) / 4), p = 3 mod 4, by
 * the chain of runs of ones the exponent is made of (223 ones, a zero,
 * 22 ones, four zeros, 11, then two squarings). 1 iff r^2 = a. */
static int fe_sqrt(uint64_t *r, const uint64_t *a) {
  u256 x2, x3, x6, x9, x11, x22, x44, x88, x176, x220, x223, t;
  fe_sqr(x2, a), fe_mul(x2, x2, a);
  fe_sqr(x3, x2), fe_mul(x3, x3, a);
  fe_sqr_n(x6, x3, 3), fe_mul(x6, x6, x3);
  fe_sqr_n(x9, x6, 3), fe_mul(x9, x9, x3);
  fe_sqr_n(x11, x9, 2), fe_mul(x11, x11, x2);
  fe_sqr_n(x22, x11, 11), fe_mul(x22, x22, x11);
  fe_sqr_n(x44, x22, 22), fe_mul(x44, x44, x22);
  fe_sqr_n(x88, x44, 44), fe_mul(x88, x88, x44);
  fe_sqr_n(x176, x88, 88), fe_mul(x176, x176, x88);
  fe_sqr_n(x220, x176, 44), fe_mul(x220, x220, x44);
  fe_sqr_n(x223, x220, 3), fe_mul(x223, x223, x3);
  fe_sqr_n(t, x223, 23), fe_mul(t, t, x22);
  fe_sqr_n(t, t, 6), fe_mul(t, t, x2);
  fe_sqr_n(r, t, 2);
  fe_sqr(t, r);
  return eq4(t, a);
}

/* --- scalars: integers mod the group order n ------------------------------- */

static const u256 SN = {0xBFD25E8CD0364141ULL, 0xBAAEDCE6AF48A03BULL, 0xFFFFFFFFFFFFFFFEULL,
                        0xFFFFFFFFFFFFFFFFULL};
static const u256 SN_HALF = {0xDFE92F46681B20A0ULL, 0x5D576E7357A4501DULL, ~0ULL,
                             0x7FFFFFFFFFFFFFFFULL}; /* n / 2, rounded down */
static const uint64_t SN_FOLD[3] = {0x402DA1732FC9BEBFULL, 0x4551231950B75FC4ULL, 1}; /* 2^256 - n */
static const u256 P_LESS_N = {0x402DA1722FC9BAEEULL, 0x4551231950B75FC4ULL, 1, 0};

/* a * b mod n. 2^256 = SN_FOLD mod n, 129 bits: each fold of the high
 * half onto the low one leaves at most 385, 259, 257 and then 256 bits. */
static void sc_mul(uint64_t *r, const uint64_t *a, const uint64_t *b) {
  uint64_t t[8];
  mul4(t, a, b);
  for (int round = 0; round < 4; round++) {
    uint64_t m[8] = {0};
    for (int i = 0; i < 4; i++) {
      uint64_t carry = 0;
      for (int j = 0; j < 3; j++) {
        u128 v = (u128)t[4 + i] * SN_FOLD[j] + m[i + j] + carry;
        m[i + j] = (uint64_t)v;
        carry = (uint64_t)(v >> 64);
      }
      m[i + 3] = carry;
    }
    u128 acc = 0;
    for (int i = 0; i < 8; i++) {
      acc += (u128)m[i] + (i < 4 ? t[i] : 0);
      t[i] = (uint64_t)acc;
      acc >>= 64;
    }
  }
  if (ge4(t, SN)) sub4(t, t, SN);
  memcpy(r, t, sizeof(u256));
}

/* 1 / a mod n for a != 0: a^(n - 2), four bits of the exponent a step. */
static void sc_inv(uint64_t *r, const uint64_t *a) {
  u256 pw[16], e, acc = {1, 0, 0, 0};
  static const u256 two = {2, 0, 0, 0};
  memcpy(pw[0], acc, sizeof(u256));
  for (int i = 1; i < 16; i++) sc_mul(pw[i], pw[i - 1], a);
  sub4(e, SN, two);
  for (int i = 63; i >= 0; i--) {
    for (int k = 0; k < 4; k++) sc_mul(acc, acc, acc);
    sc_mul(acc, acc, pw[(e[i / 16] >> (4 * (i % 16))) & 15]);
  }
  memcpy(r, acc, sizeof(u256));
}

/* --- the curve y^2 = x^3 + 7 ------------------------------------------------ */

typedef struct {
  u256 x, y;
} affine;

typedef struct {
  u256 x, y, z; /* x = X / Z^2, y = Y / Z^3; Z = 0 is the point at infinity */
} jacobian;

#define G_WINDOW 7
#define Q_WINDOW 5

/* G, 3G, 5G ... 63G */
static const affine G_ODD[1 << (G_WINDOW - 2)] = {
    {{0x59F2815B16F81798ULL, 0x029BFCDB2DCE28D9ULL, 0x55A06295CE870B07ULL, 0x79BE667EF9DCBBACULL},
     {0x9C47D08FFB10D4B8ULL, 0xFD17B448A6855419ULL, 0x5DA4FBFC0E1108A8ULL, 0x483ADA7726A3C465ULL}},
    {{0x8601F113BCE036F9ULL, 0xB531C845836F99B0ULL, 0x49344F85F89D5229ULL, 0xF9308A019258C310ULL},
     {0x6CB9FD7584B8E672ULL, 0x6500A99934C2231BULL, 0x0FE337E62A37F356ULL, 0x388F7B0F632DE814ULL}},
    {{0xCBA8D569B240EFE4ULL, 0xE88B84BDDC619AB7ULL, 0x55B4A7250A5C5128ULL, 0x2F8BDE4D1A072093ULL},
     {0xDCA87D3AA6AC62D6ULL, 0xF788271BAB0D6840ULL, 0xD4DBA9DDA6C9C426ULL, 0xD8AC222636E5E3D6ULL}},
    {{0xE92BDDEDCAC4F9BCULL, 0x3D419B7E0330E39CULL, 0xA398F365F2EA7A0EULL, 0x5CBDF0646E5DB4EAULL},
     {0xA5082628087264DAULL, 0xA813D0B813FDE7B5ULL, 0xA3178D6D861A54DBULL, 0x6AEBCA40BA255960ULL}},
    {{0xC35F110DFC27CCBEULL, 0xE09796974C57E714ULL, 0x09AD178A9F559ABDULL, 0xACD484E2F0C7F653ULL},
     {0x05CC262AC64F9C37ULL, 0xADD888A4375F8E0FULL, 0x64380971763B61E9ULL, 0xCC338921B0A7D9FDULL}},
    {{0xBBEC17895DA008CBULL, 0x5649980BE5C17891ULL, 0x5EF4246B70C65AACULL, 0x774AE7F858A9411EULL},
     {0x301D74C9C953C61BULL, 0x372DB1E2DFF9D6A8ULL, 0x0243DD56D7B7B365ULL, 0xD984A032EB6B5E19ULL}},
    {{0xDEEDDF8F19405AA8ULL, 0xB075FBC6610E58CDULL, 0xC7D1D205C3748651ULL, 0xF28773C2D975288BULL},
     {0x29B5CB52DB03ED81ULL, 0x3A1A06DA521FA91FULL, 0x758212EB65CDAF47ULL, 0x0AB0902E8D880A89ULL}},
    {{0x44ADBCF8E27E080EULL, 0x31E5946F3C85F79EULL, 0x5A465AE3095FF411ULL, 0xD7924D4F7D43EA96ULL},
     {0xC504DC9FF6A26B58ULL, 0xEA40AF2BD896D3A5ULL, 0x83842EC228CC6DEFULL, 0x581E2872A86C72A6ULL}},
    {{0x66E4FAA04A2D4A34ULL, 0xEB9898AE79B97687ULL, 0xA420FEE807EACF21ULL, 0xDEFDEA4CDB677750ULL},
     {0xCFB199F69E56EB77ULL, 0xCED1F4A04A95C0F6ULL, 0xE997B0EAD2A93DAEULL, 0x4211AB0694635168ULL}},
    {{0x7475656138385B6CULL, 0xF06ACFEBD7E86D27ULL, 0x93EF5CFF444F4979ULL, 0x2B4EA0A797A443D2ULL},
     {0xB570C854E5C09B7AULL, 0x1A01F60C50269763ULL, 0xB343083B5A1C8613ULL, 0x85E89BC037945D93ULL}},
    {{0x81340AEF25BE59D5ULL, 0x1D9AD40271F81071ULL, 0x4F93FA332CE33330ULL, 0x352BBF4A4CDD1256ULL},
     {0x67BD3D8BCF81998CULL, 0x4A1B3B2E71B1039CULL, 0xD59C18259DDA3E1FULL, 0x321EB4075348F534ULL}},
    {{0xDC9CDADD4ECACC3FULL, 0xE42AB8DFEFF5FF29ULL, 0x0230010559879124ULL, 0x2FA2104D6B38D11BULL},
     {0x423BA76B532B7D67ULL, 0x181D70ECFC882648ULL, 0xB64569335BD5DD80ULL, 0x02DE1068295DD865ULL}},
    {{0x69CA0CD7F5453714ULL, 0x263C3D84E09572E2ULL, 0xAB21A9B066EDDA83ULL, 0x9248279B09B4D68DULL},
     {0xE54A32CE97CB3402ULL, 0x3FC0DE2A887912FFULL, 0x5D1AA71BDEA2B1FFULL, 0x73016F7BF234AADEULL}},
    {{0x7E996D443DEE8729ULL, 0x2F570E144BF615C0ULL, 0x8E70132FB0BEB752ULL, 0xDAED4F2BE3A8BF27ULL},
     {0xAB40E52290BE1C55ULL, 0x3F83C230F3AFA726ULL, 0xD4A1ACA87EF8D700ULL, 0xA69DCE4A7D6C98E8ULL}},
    {{0xE6A3B5E87D22E7DBULL, 0x11ECD9E9FDF281B0ULL, 0x8ACF28D7CBB19F90ULL, 0xC44D12C7065D812EULL},
     {0xA039063F0E0E6482ULL, 0x0E106E861EDF61C5ULL, 0x76C45926C982FDACULL, 0x2119A460CE326CDCULL}},
    {{0xB61C65CBD269E6B4ULL, 0x152B695336C28063ULL, 0xC89A20CFDED60853ULL, 0x6A245BF6DC698504ULL},
     {0xFD5E6348100D8A82ULL, 0x8B33BA48D0423B6EULL, 0x8B3F5126F16A24ADULL, 0xE022CF42C2BD4A70ULL}},
    {{0xF95AE57F0D0BD6A5ULL, 0xCE13300B0BEC1146ULL, 0xC077E3D2FE541084ULL, 0x1697FFA6FD9DE627ULL},
     {0xADEE9D63D01B2396ULL, 0xA2CF15009E498AE7ULL, 0x27561506E4557433ULL, 0xB9C398F186806F5DULL}},
    {{0xF982345EF27A7479ULL, 0x9DEB8360FFB7F61DULL, 0x986D0F07E834CB0DULL, 0x605BDB019981718BULL},
     {0x3B01E1E9056B8C49ULL, 0xC26BFAE84FB14DB4ULL, 0x81A78D93EC96FE23ULL, 0x02972D2DE4F8D206ULL}},
    {{0xFE31C7E9D87FF33DULL, 0xDCB01C354959B10CULL, 0x7402FDC45A215E10ULL, 0x62D14DAB4150BF49ULL},
     {0x35F5642483B25EAFULL, 0x01AA132967AB4722ULL, 0x98088A1950EED0DBULL, 0x80FC06BD8CC5B010ULL}},
    {{0x5E555C2F86308B6FULL, 0x2C50E9F56B9B8B42ULL, 0xDE5B4B06C408E56BULL, 0x80C60AD0040F27DAULL},
     {0x1AA01F56430BD57AULL, 0xA65EED4CBE7024EBULL, 0x26E66BAD7FE72F70ULL, 0x1C38303F1CC5C30FULL}},
    {{0x9D5EABB0FA03C8FBULL, 0x4CC5DC9487D84704ULL, 0xAA74C6348CC54D34ULL, 0x7A9375AD6167AD54ULL},
     {0x02D499EC224DC7F7ULL, 0xBDC59EA10C70CE2BULL, 0x09559E0D79269046ULL, 0x0D0E3FA9ECA87269ULL}},
    {{0x4BB51F459BC3FFC9ULL, 0xBB408EC39B68DF50ULL, 0x907A9ED045447A79ULL, 0xD528ECD9B696B54CULL},
     {0x063465B521409933ULL, 0xBC4345405C520DBCULL, 0x9966F21881FD656EULL, 0xEECF41253136E5F9ULL}},
    {{0x87231808F8B45963ULL, 0x5266115E4A7ECB13ULL, 0xEA25F514E8ECDAD0ULL, 0x049370A4B5F43412ULL},
     {0xB653052A12949C9AULL, 0x54C3F3AFBB5B6764ULL, 0x8B3081B0512FD62AULL, 0x758F3F41AFD6ED42ULL}},
    {{0xF1C13EB1FC345D74ULL, 0x881D811E0E1498E2ULL, 0xD73DF930D64702EFULL, 0x77F230936EE88CBBULL},
     {0xBE8EB3C7671C60D6ULL, 0x96C95330D97077CBULL, 0x0A08266E9BA1B378ULL, 0x958EF42A7886B640ULL}},
    {{0xEB28531B7739F530ULL, 0x58C80074AB9D4DBAULL, 0xEA44887E5C7C0BCEULL, 0xF2DAC991CC4CE4B9ULL},
     {0x1A117DBA703A3C37ULL, 0x9EB5FBEB0598E4FDULL, 0x4DA1F32DEC2531DFULL, 0xE0DEDC9B3B2F8DADULL}},
    {{0xBCBA4850C690D45BULL, 0x5A216CDFC9DAE3DEULL, 0x1B4BE8FBBE252012ULL, 0x463B3D9F662621FBULL},
     {0x1CB377B01AF7307EULL, 0xC622E27C970A1DE3ULL, 0x43114306DD8622D7ULL, 0x5ED430D78C296C35ULL}},
    {{0xA32496B49998F247ULL, 0x6B98FAC14328A2D1ULL, 0x09232D4AFF3B5997ULL, 0xF16F804244E46E2AULL},
     {0xD6579962C4E31DF6ULL, 0x2A6C53C26E5CCE26ULL, 0x13D206FCDF4E33D9ULL, 0xCEDABD9B82203F7EULL}},
    {{0x369E15F7151D41D1ULL, 0x5D245315ACE27C65ULL, 0xB0352B7A14311AF5ULL, 0xCAF754272DC84563ULL},
     {0xC32F908318A04476ULL, 0x5F4FA9B7962232A5ULL, 0xA41B643FA5E46057ULL, 0xCB474660EF35F5F2ULL}},
    {{0x24497BC86F082120ULL, 0x44A09C07CB86D7C1ULL, 0xF85D0F1709979D8BULL, 0x2600CA4B282CB986ULL},
     {0x4B0BE9475A7E4B40ULL, 0x5AC6BE74AB5F0EF4ULL, 0xA693B03FCDDBB45DULL, 0x4119B88753C15BD6ULL}},
    {{0xC602A7746998E435ULL, 0x01C48685E24F7DC8ULL, 0x338EC53CD12220BCULL, 0x7635CA72D7E8432CULL},
     {0xD9E76F302C5B9C61ULL, 0x4ECFC061D57048BAULL, 0x3D1D5E590F78E6D7ULL, 0x091B649609489D61ULL}},
    {{0xC1A50743BF56CC18ULL, 0xB7F2B33479D468FBULL, 0xDBBF4A87DEEE8A66ULL, 0x754E3239F325570CULL},
     {0x0C5D98093C536683ULL, 0x23EE33D0197A695DULL, 0xB3CD0ED304EA49A0ULL, 0x0673FB86E5BDA30FULL}},
    {{0x9FE2694691D9B9E8ULL, 0x330800661D1C952FULL, 0xFF57859C82D570F0ULL, 0xE3E6BD1071A1E96AULL},
     {0x67002AF4920E37F5ULL, 0xA5A2283993E90C41ULL, 0x40C0AA58379A3CB6ULL, 0x59C9E0BBA394E76FULL}},
};

/* 2P. No point of this curve has y = 0 (its order is odd), and the
 * double of infinity is infinity: Z stays 0. */
static void point_double(jacobian *r, const jacobian *p) {
  u256 m, s, yy, t, x3;
  fe_sqr(m, p->x);
  fe_add(t, m, m), fe_add(m, t, m); /* M = 3 X^2 */
  fe_sqr(yy, p->y);
  fe_mul(s, p->x, yy);
  fe_add(s, s, s), fe_add(s, s, s); /* S = 4 X Y^2 */
  fe_mul(t, p->y, p->z);
  fe_add(r->z, t, t); /* Z3 = 2 Y Z */
  fe_sqr(x3, m);
  fe_sub(x3, x3, s), fe_sub(x3, x3, s); /* X3 = M^2 - 2 S */
  fe_sqr(yy, yy);
  fe_add(yy, yy, yy), fe_add(yy, yy, yy), fe_add(yy, yy, yy); /* 8 Y^4 */
  fe_sub(t, s, x3);
  fe_mul(t, m, t);
  fe_sub(r->y, t, yy); /* Y3 = M (S - X3) - 8 Y^4 */
  memcpy(r->x, x3, sizeof(u256));
}

/* The shared end of both additions: from P1 = (x1, y1, .), H = U2 - U1,
 * R = S2 - S1 and the new Z. H = 0 means the two points have one x:
 * the same point (R = 0: its double) or opposite ones (infinity). */
static void point_add_finish(jacobian *r, const jacobian *p1, const uint64_t *u1,
                             const uint64_t *s1, const uint64_t *h, const uint64_t *rr,
                             const uint64_t *z3) {
  u256 hh, hhh, v, t, x3;
  if (is_zero4(h)) {
    if (is_zero4(rr))
      point_double(r, p1);
    else
      memset(r, 0, sizeof(*r));
    return;
  }
  fe_sqr(hh, h);
  fe_mul(hhh, h, hh);
  fe_mul(v, u1, hh);
  fe_sqr(x3, rr);
  fe_sub(x3, x3, hhh), fe_sub(x3, x3, v), fe_sub(x3, x3, v); /* X3 = R^2 - H^3 - 2 V */
  fe_sub(t, v, x3);
  fe_mul(t, rr, t);
  fe_mul(hhh, s1, hhh);
  fe_sub(r->y, t, hhh); /* Y3 = R (V - X3) - S1 H^3 */
  memcpy(r->x, x3, sizeof(u256));
  memcpy(r->z, z3, sizeof(u256));
}

/* P + Q, Q affine and not infinity. */
static void point_add_affine(jacobian *r, const jacobian *p, const affine *q) {
  u256 zz, u2, s2, h, rr, z3;
  if (is_zero4(p->z)) {
    memcpy(r->x, q->x, sizeof(u256));
    memcpy(r->y, q->y, sizeof(u256));
    memset(r->z, 0, sizeof(u256));
    r->z[0] = 1;
    return;
  }
  fe_sqr(zz, p->z);
  fe_mul(u2, q->x, zz);
  fe_mul(s2, p->z, zz), fe_mul(s2, q->y, s2);
  fe_sub(h, u2, p->x);
  fe_sub(rr, s2, p->y);
  fe_mul(z3, p->z, h);
  point_add_finish(r, p, p->x, p->y, h, rr, z3);
}

/* P + Q, both Jacobian. */
static void point_add(jacobian *r, const jacobian *p, const jacobian *q) {
  u256 z1z1, z2z2, u1, u2, s1, s2, h, rr, z3;
  if (is_zero4(p->z)) {
    memmove(r, q, sizeof(*r));
    return;
  }
  if (is_zero4(q->z)) {
    memmove(r, p, sizeof(*r));
    return;
  }
  fe_sqr(z1z1, p->z), fe_sqr(z2z2, q->z);
  fe_mul(u1, p->x, z2z2), fe_mul(u2, q->x, z1z1);
  fe_mul(s1, q->z, z2z2), fe_mul(s1, p->y, s1);
  fe_mul(s2, p->z, z1z1), fe_mul(s2, q->y, s2);
  fe_sub(h, u2, u1);
  fe_sub(rr, s2, s1);
  fe_mul(z3, p->z, q->z), fe_mul(z3, z3, h);
  point_add_finish(r, p, u1, s1, h, rr, z3);
}

/* The key's point from its 33 compressed bytes; 0 if they name none. */
static int point_decode(affine *q, const uint8_t *key) {
  u256 rhs;
  static const u256 seven = {7, 0, 0, 0};
  if (key[0] != 2 && key[0] != 3) return 0;
  load_be(q->x, key + 1);
  if (ge4(q->x, FP)) return 0;
  fe_sqr(rhs, q->x), fe_mul(rhs, rhs, q->x), fe_add(rhs, rhs, seven);
  if (!fe_sqrt(q->y, rhs)) return 0;
  if ((q->y[0] & 1) != (key[0] & 1)) fe_neg(q->y, q->y);
  return 1;
}

/* k in width-w non-adjacent form, least significant digit first: every
 * digit 0 or odd with |d| < 2^(w - 1), and w - 1 zeros after each that
 * is not 0. At most 257 digits; the number written is returned. */
static int wnaf(int8_t digits[257], const uint64_t *k, int w) {
  uint64_t v[5] = {k[0], k[1], k[2], k[3], 0};
  int len = 0;
  memset(digits, 0, 257);
  while (v[0] | v[1] | v[2] | v[3] | v[4]) {
    if (v[0] & 1) {
      int d = (int)(v[0] & ((1u << w) - 1));
      if (d >= 1 << (w - 1)) d -= 1 << w;
      digits[len] = (int8_t)d;
      if (d > 0) {
        v[0] -= (uint64_t)d; /* the low w bits are d: no borrow */
      } else {
        u128 c = (u128)v[0] + (uint64_t)(-d);
        v[0] = (uint64_t)c;
        for (int i = 1; i < 5 && (c >>= 64); i++) {
          c += v[i];
          v[i] = (uint64_t)c;
        }
      }
    }
    for (int i = 0; i < 4; i++) v[i] = (v[i] >> 1) | (v[i + 1] << 63);
    v[4] >>= 1;
    len++;
  }
  return len;
}

/* u1 G + u2 Q in one pass over both scalars' digits. */
static void double_mult(jacobian *r, const uint64_t *u1, const uint64_t *u2, const affine *q) {
  int8_t d1[257], d2[257];
  jacobian q_odd[1 << (Q_WINDOW - 2)], twice, neg;
  affine g;
  int n1 = wnaf(d1, u1, G_WINDOW), n2 = wnaf(d2, u2, Q_WINDOW);

  memcpy(q_odd[0].x, q->x, sizeof(u256));
  memcpy(q_odd[0].y, q->y, sizeof(u256));
  memset(q_odd[0].z, 0, sizeof(u256));
  q_odd[0].z[0] = 1;
  point_double(&twice, &q_odd[0]);
  for (int i = 1; i < 1 << (Q_WINDOW - 2); i++) point_add(&q_odd[i], &q_odd[i - 1], &twice);

  memset(r, 0, sizeof(*r));
  for (int i = (n1 > n2 ? n1 : n2) - 1; i >= 0; i--) {
    point_double(r, r);
    if (d1[i] > 0) {
      point_add_affine(r, r, &G_ODD[d1[i] >> 1]);
    } else if (d1[i] < 0) {
      g = G_ODD[-d1[i] >> 1];
      fe_neg(g.y, g.y);
      point_add_affine(r, r, &g);
    }
    if (d2[i] > 0) {
      point_add(r, r, &q_odd[d2[i] >> 1]);
    } else if (d2[i] < 0) {
      neg = q_odd[-d2[i] >> 1];
      fe_neg(neg.y, neg.y);
      point_add(r, r, &neg);
    }
  }
}

/* One lane whose r and s are in range: w = 1 / s mod n. */
static int verify_lane(const uint8_t *key, const uint8_t *digest, const uint64_t *r,
                       const uint64_t *w) {
  affine q;
  jacobian sum;
  u256 e, u1, u2, zz, t;
  if (!point_decode(&q, key)) return 0;
  load_be(e, digest);
  if (ge4(e, SN)) sub4(e, e, SN);
  sc_mul(u1, e, w);
  sc_mul(u2, r, w);
  double_mult(&sum, u1, u2, &q);
  if (is_zero4(sum.z)) return 0;
  /* R.x mod n = r without the inversion: X = r Z^2, or, where r + n is
   * still a field element, X = (r + n) Z^2 */
  fe_sqr(zz, sum.z);
  fe_mul(t, r, zz);
  if (eq4(t, sum.x)) return 1;
  if (ge4(r, P_LESS_N)) return 0;
  add4(t, r, SN);
  fe_mul(t, t, zz);
  return eq4(t, sum.x);
}

#define BLOCK 64 /* lanes that share one inversion mod n */

#endif /* __SIZEOF_INT128__ */

/* n lanes: keys 33 bytes a lane, digests 32 (SHA-256 of the signed
 * bytes), sigs 64 (r || s, big-endian); out[i] = 1 where lane i's
 * signature is good, else 0. Returns 1, or 0 with nothing written where
 * this was built without 128-bit integers. */
int secp256k1_ecdsa_verify_batch(const uint8_t *keys, const uint8_t *digests,
                                 const uint8_t *sigs, int64_t n, uint8_t *out) {
#ifdef __SIZEOF_INT128__
  for (int64_t lo = 0; lo < n; lo += BLOCK) {
    int m = (int)(n - lo < BLOCK ? n - lo : BLOCK);
    u256 r[BLOCK], s[BLOCK], prefix[BLOCK], inv;
    /* the range rules, and one inversion for the block: the running
     * products of every s in range, the last one inverted, and walked
     * back (Montgomery's trick) */
    u256 run = {1, 0, 0, 0};
    for (int i = 0; i < m; i++) {
      load_be(r[i], sigs + (lo + i) * 64);
      load_be(s[i], sigs + (lo + i) * 64 + 32);
      out[lo + i] = !is_zero4(r[i]) && !ge4(r[i], SN) && !is_zero4(s[i]) && ge4(SN_HALF, s[i]);
      memcpy(prefix[i], run, sizeof(u256));
      if (out[lo + i]) sc_mul(run, run, s[i]);
    }
    sc_inv(inv, run);
    for (int i = m - 1; i >= 0; i--) { /* s[i] becomes 1 / s[i] */
      u256 w;
      if (!out[lo + i]) continue;
      sc_mul(w, inv, prefix[i]);
      sc_mul(inv, inv, s[i]);
      memcpy(s[i], w, sizeof(u256));
    }
    for (int i = 0; i < m; i++)
      if (out[lo + i])
        out[lo + i] = (uint8_t)verify_lane(keys + (lo + i) * 33, digests + (lo + i) * 32, r[i], s[i]);
  }
  return 1;
#else
  (void)keys, (void)digests, (void)sigs, (void)n, (void)out;
  return 0;
#endif
}
